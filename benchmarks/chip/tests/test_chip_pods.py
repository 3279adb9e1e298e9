"""The four-chip cell's faults come out not correct.

Four fake CPU devices in a child process (the device count is fixed when
JAX starts), two pods of two, smoke size, under the cell's own limits: the
sound ``hier_int8`` run is correct, and each of these runs is not:

* ``no_exchange``: ``sync_hier_int8`` replaced by a pod-local
  compress/decompress, so no gradient crosses the pods;
* ``state_unchanged``: the step returns the state it was given;
* ``half_batch``: the step leaves half of the batch out and takes the mean
  over the rest.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))

from tiny_cells import ROOT  # noqa: E402

FAULTS = ("no_exchange", "state_unchanged", "half_batch")


@pytest.fixture(scope="module")
def four_chip_runs():
    code = textwrap.dedent(
        f"""
        import json, sys
        sys.path[:0] = [{str(Path(__file__).resolve().parent)!r}, {str(ROOT)!r}, {str(ROOT / "src")!r}]
        import jax, pytest
        from test_chip_faults import _wrap_step
        from tiny_cells import TRAIN4, tiny_spec
        from benchmarks.chip import counts, harness
        from repro.distributed import compression, steps

        counts.PEAKS[jax.devices()[0].device_kind] = counts.PEAKS["TPU v5 lite"]

        def local_only(grads, ef, axis="pod"):
            boosted = compression.apply_error_feedback(grads, ef)
            sent = jax.tree.map(lambda g: compression.int8_decompress(compression.int8_compress(g)), boosted)
            return sent, compression.residual(boosted, sent)

        out = {{}}
        for name in ("sound",) + {FAULTS!r}:
            mp = pytest.MonkeyPatch()
            if name == "no_exchange":
                mp.setattr(steps, "sync_hier_int8", local_only)
            elif name != "sound":
                _wrap_step(mp, name)
            out[name] = harness.execute(tiny_spec(TRAIN4), jax.devices()[:4], 11, 0.3, False, 0.0)["correct"]
            mp.undo()
        print(json.dumps(out))
        """
    )
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4", JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_dropped_pod_exchange_is_not_correct(four_chip_runs):
    assert (four_chip_runs["sound"], four_chip_runs["no_exchange"]) == (True, False)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_four_chip_step_fault_is_not_correct(four_chip_runs, fault):
    assert four_chip_runs[fault] is False
