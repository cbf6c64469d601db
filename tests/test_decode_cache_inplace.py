"""The decode step writes only its new KV rows.

Compiled on the CPU at reduced size, ``jax.jit(decode_step,
donate_argnums=(1,))`` aliases the whole donated cache to its output,
and its optimized HLO moves no cache-sized data besides the rows it
writes: no ``copy``, ``broadcast`` or ``dynamic-update-slice`` outside a
fusion whose result has the shape of a whole stacked cache leaf or of
one layer's leaf (a layer's slice copied out, a zero-filled stacked
output, the stack rebuilt from per-layer results).  Inside a fusion such
an op is the in-place write itself or arithmetic of that shape (a Mamba
state's update), and is not counted.  Undonated, the step aliases
nothing and copies each leaf once, whole, into the buffer it returns.

Sharded over a mesh of host devices (the serve step the dry run
compiles), the row writes move no cache between devices: no collective
carries the cache's position axis.
"""

import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_reduced_config, with_pipeline
from repro.models import build_model

# jamba at three attention periods: a stage of one super-layer would let
# XLA drop the layer loop
CASES = {
    "smollm-135m": lambda: with_pipeline(get_reduced_config("smollm-135m")),
    "deepseek-v3-671b": lambda: get_reduced_config("deepseek-v3-671b"),
    "jamba-1.5-large-398b": lambda: get_reduced_config(
        "jamba-1.5-large-398b", n_layers=24),
}
MOVES = ("copy", "broadcast", "dynamic-update-slice")
INSTR = re.compile(r"\s*(?:ROOT )?%[\w.\-]+ = \w+\[([\d,]*)\]\S* ([\w\-]+)\(")


def _unfused_ops(hlo: str):
    """``(op, dims)`` of every instruction outside a fusion's body."""
    fused = set(re.findall(r"kind=k\w+, calls=(%[\w.\-]+)", hlo))
    ops, inside = [], False
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) ", line)
        if head and line.rstrip().endswith("{"):
            inside = head.group(1) in fused
            continue
        m = INSTR.match(line)
        if m and not inside:
            ops.append((m.group(2), m.group(1)))
    return ops


@pytest.fixture(scope="module",
                params=[(a, d) for a in CASES for d in (True, False)],
                ids=lambda p: f"{p[0]}-{'donated' if p[1] else 'undonated'}")
def compiled(request):
    arch, donate = request.param
    model = build_model(CASES[arch]())
    params, _ = model.init_params(jax.random.key(0))
    b, max_len = 2, 16
    caches = model.init_cache(b, max_len)
    step = jax.jit(model.decode_step, donate_argnums=(1,) if donate else ())
    exe = step.lower(params, caches, jnp.zeros((b, 1), jnp.int32),
                     jnp.zeros((b,), jnp.int32)).compile()
    return exe, jax.tree.leaves(caches), donate


def test_donated_decode_aliases_the_whole_cache(compiled):
    exe, leaves, donate = compiled
    nbytes = sum(a.nbytes for a in leaves) if donate else 0
    assert exe.memory_analysis().alias_size_in_bytes == nbytes


def test_decode_moves_no_cache_sized_data(compiled):
    exe, leaves, donate = compiled
    shapes = {",".join(map(str, s)) for a in leaves
              for s in (a.shape, a.shape[1:])}
    moved = [(op, dims) for op, dims in _unfused_ops(exe.as_text())
             if op in MOVES and dims in shapes]
    # undonated: one whole copy of each leaf, the buffer the step returns
    whole = [] if donate else sorted(
        ("copy", ",".join(map(str, a.shape))) for a in leaves)
    assert sorted(moved) == whole, moved


# the serve step sharded over 8 host devices (batch over "data", heads
# over "model"; at batch 1 the cache's position axis over "data"), with a
# position axis of 40 that no weight has
SHARDED = [("smollm-135m", 8, (2, 4)), ("smollm-135m", 1, (4, 2)),
           ("deepseek-v3-671b", 8, (2, 4)),
           ("jamba-1.5-large-398b", 8, (2, 4))]
_SHARDED_SCRIPT = textwrap.dedent("""
    import json, re, sys
    import jax
    from repro.configs import ShapeSpec, get_reduced_config
    from repro.launch.mesh import make_debug_mesh
    from repro.launch.steps import make_step
    from repro.runtime import sharding as SH
    out = {}
    for arch, b, mesh_shape in json.loads(sys.argv[1]):
        mesh = make_debug_mesh(tuple(mesh_shape), ("data", "model"))
        with SH.use_mesh(mesh):
            step, args, shardings = make_step(
                get_reduced_config(arch), ShapeSpec("t", 40, b, "decode"))
            hlo = jax.jit(step, in_shardings=shardings(mesh)).lower(
                *args).compile().as_text()
        out[f"{arch}/{b}"] = re.findall(
            r"= \\w+\\[([\\d,]*)\\]\\S* (all-gather|all-reduce|all-to-all"
            r"|collective-permute|reduce-scatter)", hlo)
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def sharded_collectives():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    p = subprocess.run([sys.executable, "-c", _SHARDED_SCRIPT,
                        json.dumps(SHARDED)], env=env, capture_output=True,
                       text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.splitlines()[-1])


@pytest.mark.parametrize("arch,b,mesh", SHARDED,
                         ids=[f"{a}-b{b}" for a, b, _ in SHARDED])
def test_sharded_decode_moves_no_cache_between_devices(
        sharded_collectives, arch, b, mesh):
    colls = sharded_collectives[f"{arch}/{b}"]
    assert colls  # the weights' collectives are read
    cache_sized = [c for c in colls if "40" in c[0].split(",")]
    assert not cache_sized, cache_sized
