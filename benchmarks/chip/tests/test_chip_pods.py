"""A two-pod run whose cross-pod exchange is left out comes out not correct.

Four fake CPU devices in a child process (the device count is fixed when
JAX starts), two pods of two, smoke size: the sound ``hier_int8`` run is
correct under the cell's limits, and the same run with ``sync_hier_int8``
replaced by a pod-local compress/decompress is not.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from tiny_cells import ROOT  # noqa: E402


def test_dropped_pod_exchange_is_not_correct():
    code = textwrap.dedent(
        f"""
        import json, sys
        sys.path[:0] = [{str(Path(__file__).resolve().parent)!r}, {str(ROOT)!r}, {str(ROOT / "src")!r}]
        import jax
        from tiny_cells import TRAIN4, tiny_spec
        from benchmarks.chip import counts, harness
        from repro.distributed import compression, steps

        counts.PEAKS[jax.devices()[0].device_kind] = counts.PEAKS["TPU v5 lite"]

        def local_only(grads, ef, axis="pod"):
            boosted = compression.apply_error_feedback(grads, ef)
            sent = jax.tree.map(lambda g: compression.int8_decompress(compression.int8_compress(g)), boosted)
            return sent, compression.residual(boosted, sent)

        out = {{}}
        for name in ("sound", "no_exchange"):
            if name == "no_exchange":
                steps.sync_hier_int8 = local_only
            spec = tiny_spec(TRAIN4)
            out[name] = harness.execute(spec, jax.devices()[:4], 11, 0.3, False, 0.0)["correct"]
        print(json.dumps(out))
        """
    )
    env = dict(os.environ, XLA_FLAGS="--xla_force_host_platform_device_count=4", JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True, env=env, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result == {"sound": True, "no_exchange": False}
