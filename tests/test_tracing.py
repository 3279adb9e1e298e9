"""The program's own trace annotations: the runtime loop's host spans in a
profiler trace, and the named scopes the compiled programs carry.

Scopes are metadata only: with every ``jax.named_scope`` turned into a no-op
the compiled programs must be the same once their debug information and
instruction names are stripped.
"""

import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_smoke_config
from repro.distributed import init_train_state, make_train_step
from repro.distributed.sync import sync_hier, sync_hier_int8
from repro.launch.mesh import make_host_mesh
from repro.launch.shapes import params_specs
from repro.models import decode_step, init_decode_cache, prefill
from repro.optim import AdamWConfig
from repro.runtime import GeoTrainer, TrainerConfig
from repro.runtime import trainer as trainer_mod

MODEL = "distilgpt2-82m"
B, S = 2, 16
PHASES = [
    trainer_mod.SPAN_FEED,
    trainer_mod.SPAN_DISPATCH,
    trainer_mod.SPAN_FETCH,
    trainer_mod.SPAN_BOOKKEEP,
    trainer_mod.SPAN_CALLBACK,
]


# -- host spans of the runtime loop ---------------------------------------------


def _host_spans(log_dir):
    """(name, start, end, stats) of every ``repro.`` event on the host."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("repro."):
                        out.append((ev.name, ev.start_ns, ev.end_ns, dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


def test_every_step_is_one_span_with_its_phases_in_order(tmp_path):
    steps = 4
    tc = TrainerConfig(
        seq_len=S, global_batch=B, steps=steps, strategy="hier",
        checkpoint_every=100, log_every=100,
    )
    trainer = GeoTrainer(
        get_smoke_config(MODEL), make_host_mesh(), trainer_cfg=tc,
        checkpoint_dir=str(tmp_path / "ckpt"),
    )
    seen = []
    with jax.profiler.trace(str(tmp_path / "trace")):
        trainer.run(on_step=lambda step, row: seen.append(step))
    assert seen == list(range(steps))

    spans = _host_spans(str(tmp_path / "trace"))
    step_spans = [e for e in spans if e[0] == trainer_mod.SPAN_STEP]
    assert [int(e[3]["step_num"]) for e in step_spans] == list(range(steps))
    for k, (_, lo, hi, _) in enumerate(step_spans):
        inner = [e for e in spans if e[0] != trainer_mod.SPAN_STEP and lo <= e[1] and e[2] <= hi]
        names = [e[0] for e in inner]
        expect = PHASES + ([trainer_mod.SPAN_CHECKPOINT] if k == steps - 1 else [])
        assert names == expect, (k, names)
        assert all(a[2] <= b[1] for a, b in zip(inner, inner[1:])), "phases overlap"
    # the final wait for the checkpoint writer, after the last step
    last = [e for e in spans if e[1] >= step_spans[-1][2]]
    assert [e[0] for e in last] == [trainer_mod.SPAN_CHECKPOINT]


def test_the_result_keeps_its_keys_and_drops_wan_phases(tmp_path):
    tc = TrainerConfig(seq_len=S, global_batch=B, steps=1, checkpoint_every=100, log_every=100)
    result = GeoTrainer(
        get_smoke_config(MODEL), make_host_mesh(), trainer_cfg=tc, checkpoint_dir=str(tmp_path)
    ).run()
    assert "wan_phases" not in result
    assert {"final_loss", "metrics", "last_checkpoint"} <= set(result)
    assert result["metrics"][0]["grad_norm"] > 0


# -- named scopes in the compiled programs ----------------------------------------


def _train_hlo(cfg, mesh):
    batch = {k: jax.ShapeDtypeStruct((B, S), jnp.int32) for k in ("tokens", "labels")}
    p = params_specs(cfg)
    step, _ = make_train_step(cfg, mesh, params_shapes=p, batch_shapes=batch, donate=False)
    state = jax.eval_shape(lambda q: init_train_state(q, AdamWConfig()), p)
    return step.lower(p, state, batch).compile().as_text()


def _serve_hlo(cfg):
    p = params_specs(cfg)
    tokens = jax.ShapeDtypeStruct((B, S), jnp.int32)
    pre = jax.jit(lambda q, t: prefill(q, {"tokens": t}, cfg, max_len=S + 4))
    cache = jax.eval_shape(lambda: init_decode_cache(cfg, B, S + 4))
    dec = jax.jit(lambda q, t, c, pos: decode_step(q, t, c, cfg, pos))
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return (pre.lower(p, tokens).compile().as_text(),
            dec.lower(p, tok, cache, pos).compile().as_text())


def _sync_hlo():
    mesh = Mesh(np.array(jax.devices()[:1]), ("pod",))
    g = {"w": jax.ShapeDtypeStruct((4, 256), jnp.float32)}

    def both(grads, ef):
        return sync_hier(grads, num_channels=2), sync_hier_int8(grads, ef)

    fn = jax.jit(jax.shard_map(both, mesh=mesh, in_specs=P(), out_specs=P(), check_vma=False))
    return fn.lower(g, g).compile().as_text()


def _programs():
    cfg = get_smoke_config(MODEL)
    mesh = make_host_mesh()
    with mesh:
        train = _train_hlo(cfg, mesh)
    prefill_hlo, decode_hlo = _serve_hlo(cfg)
    return {"train": train, "prefill": prefill_hlo, "decode": decode_hlo, "sync": _sync_hlo()}


def _scopes(hlo: str):
    """Every component of every ``op_name`` path, with transformations
    (``transpose(jvp(head))``) peeled off."""
    out = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo):
        for comp in path.split("/"):
            while (m := re.fullmatch(r"[\w.\-]+\((.*)\)", comp)) is not None:
                comp = m.group(1)
            out.add(comp)
    return out


def _bare(hlo: str) -> str:
    """The program without its debug information: each instruction's
    ``metadata``, the module's table of source files and stack frames, and
    the instructions' names (numbered in order of first use instead)."""
    hlo = re.sub(r"\nFileNames\n.*?\n(?=%|ENTRY)", "\n", hlo, flags=re.S)
    hlo = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)
    names = {}
    return re.sub(r"%[\w.\-]+", lambda m: names.setdefault(m.group(0), f"%v{len(names)}"), hlo)


@pytest.fixture(scope="module")
def programs():
    return _programs()


@pytest.mark.parametrize("program,scopes", [
    ("train", {"embed", "layers", "attention", "ffn", "head", "loss", "adamw"}),
    ("prefill", {"prefill", "embed", "layers", "attention", "ffn", "head", "kv_cache"}),
    ("decode", {"decode", "embed", "layers", "attention", "ffn", "head", "kv_cache"}),
    ("sync", {"sync", "wan_int8"}),
])
def test_compiled_programs_carry_the_scopes(programs, program, scopes):
    assert scopes <= _scopes(programs[program])


def test_scopes_leave_the_compiled_programs_unchanged(programs, monkeypatch):
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _programs()
    scopes = {"embed", "layers", "attention", "ffn", "head", "loss", "adamw", "kv_cache",
              "prefill", "decode", "sync", "wan_int8"}
    for name, hlo in bare.items():
        assert not scopes & _scopes(hlo), name  # compiled afresh, without scopes
        assert _bare(hlo) == _bare(programs[name]), name
