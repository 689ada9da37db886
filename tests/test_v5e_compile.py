"""Compile the served path for a TPU v5e that is described, not attached.

The chip's compiler refuses what interpret mode lets through: block shapes
off the (8, 128) tiling, layouts Mosaic cannot cast, programs that do not
fit the device's memory.  These compiles catch that here, at the widths of
``chip_smoke.py`` (m = 300, h = 48, B = 64), at no chip time.  Nothing runs,
so they say nothing about results or times.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.kernels import ops

M, H, B = 300, 48, 64
#: The smoke corpus's resident rows and restricted vocabulary (Set-2 widths,
#: n cut to 65,536; v_e is what its seeded generator yields).
N_DOCS, V_E = 65_536, 283_696
HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler, or it cannot be loaded here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_phase1_kernel_compiles(one_chip):
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,  # noqa: E731
                                            sharding=one_chip)
    c = _compile(
        lambda e, t, v: ops.lc_rwmd_phase1_pregathered(e, t, v,
                                                       interpret=False),
        s(32_768, M), s(B, H, M), s(B, H))
    assert "tpu_custom_call" in c.as_text()


def test_spmm_blocked_kernel_compiles(one_chip):
    c = _compile(
        lambda i, w, z: ops.spmm_ell(i, w, z, interpret=False),
        jax.ShapeDtypeStruct((8_192, H), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((8_192, H), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((32_768, B), jnp.float32, sharding=one_chip))
    assert "tpu_custom_call" in c.as_text()


def test_sinkhorn_kernel_compiles(one_chip):
    s = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,  # noqa: E731
                                            sharding=one_chip)
    c = _compile(
        lambda t1, w1, t2, w2: ops.sinkhorn_wmd(
            t1, w1, t2, w2, eps=0.02, eps_scaling=3, max_iters=200,
            interpret=False),
        s(128, H, M), s(128, H), s(128, H, M), s(128, H))
    assert "tpu_custom_call" in c.as_text()


@pytest.fixture(scope="module")
def served_step(topo):
    """The segmented serve step at the smoke corpus's sizes, compiled for
    one described v5e chip."""
    from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

    from repro.distributed.lcrwmd_dist import (
        _segmented_step, _slab_geometry, _width_classes)
    from repro.launch.mesh import DATA_AXIS, MODEL_AXIS

    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1),
                (DATA_AXIS, MODEL_AXIS), axis_types=(AxisType.Auto,) * 2)
    rb, g, _ = _slab_geometry(N_DOCS, 1, 128, 8)
    step = _segmented_step(mesh, kc=16, rbs=(rb,), gs=(g,),
                           widths=(_width_classes(H),), self_exclude=False,
                           bf16_matmul=False, phase1_full_mesh=True)

    def s(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    args = ((s((N_DOCS, H), jnp.int32, DATA_AXIS, None),),
            (s((N_DOCS, H), jnp.float32, DATA_AXIS, None),),
            (s((N_DOCS,), jnp.bool_, DATA_AXIS),),
            (s((N_DOCS,), jnp.int32, DATA_AXIS),),
            (s((N_DOCS // (rb * g),), jnp.int32, DATA_AXIS),),
            s((1,), jnp.int32, None),
            s((B, H, M), jnp.float32, None, None, None),
            s((B, H), jnp.float32, None, None),
            s((B,), jnp.int32, None),
            (s((V_E, M), jnp.float32, (MODEL_AXIS, DATA_AXIS), None),))
    return step.lower(*args).compile()


def test_served_segmented_step_fits_one_chip(served_step):
    mem = served_step.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert used < HBM_BYTES, f"{used / 2**30:.2f} GiB of 16 GiB"


def test_width_class_branches_read_z_in_place(served_step):
    """Z enters each slab's width-class branch by reference: its one copy
    is the layout change after phase 1, none per slab."""
    text = served_step.as_text()
    assert "conditional(" in text
    assert len(re.findall(rf"= f32\[{V_E},{B}\]\S* copy\(", text)) <= 1


def test_phase1_runs_per_query_group(served_step):
    """Phase 1 makes no (V_E, B·H) block: each group of 16 queries has a
    GEMM per width class, whose N = 16·w fills whole 128-lane tiles."""
    from repro.distributed.lcrwmd_dist import _width_classes

    text = served_step.as_text()
    assert f"f32[{V_E},{B * H}]" not in text
    dots = re.findall(r"= f32\[(\d+),(\d+)\]\S* (?:convolution|dot)\("
                      r"[^\n]*op_name=\"([^\"]+)\"", text)
    n = sorted(int(c) for v, c, name in dots
               if "/phase1/" in name and int(v) == V_E)
    assert n == [16 * w for w in _width_classes(H)], n
    assert all(c % 128 == 0 for c in n)


def test_wmd_rerank_fits_and_repeats_no_query_tensor(one_chip):
    """The cascade's rerank at ``set2_wmd``'s sizes (B 64, kc 32, h 48,
    m 300, the full vocabulary): it fits one chip, and the only
    (B·kc·h, m) tensor it makes is the candidates' gathered embeddings —
    each query's are broadcast over its candidates inside the cost."""
    from repro.core.lc_rwmd import _segmented_rerank

    kc, vocab = 32, 292_492

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    sink = tuple(sorted(dict(eps=0.02, eps_scaling=3, max_iters=200).items()))
    c = _segmented_rerank.lower(
        16, sink, s((vocab, M)), s((B * kc, H), jnp.int32), s((B * kc, H)),
        s((B, H, M)), s((B, H)), s((B, kc), jnp.int32),
        s((B, kc), jnp.bool_)).compile()
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES
    entry = c.as_text().split("\nENTRY ", 1)[1].split("\n}", 1)[0]
    big = []
    for dims, op in re.findall(r"= f32\[([\d,]+)\]\S* (\w[\w-]*)\(", entry):
        if op != "bitcast" and np.prod([int(d) for d in dims.split(",")]) \
                == B * kc * H * M:
            big.append((dims, op))
    assert len(big) == 1, big
