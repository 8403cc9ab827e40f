"""The main-path programs compile for a TPU v5e chip.

Nothing runs here: each kernel (``interpret=False``) and each plan
micro-batch executable is lowered with abstract operands placed on one
chip of a *described* ``v5e:2x2`` topology and compiled by the TPU
compiler, at the widths ``chip_smoke.py`` drives.  A block shape or a
layout the chip's compiler refuses fails here, on a host without a chip.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import (ArchSpec, Builder, CamType, Module, PassManager,
                        TensorType, compile_fn, get_plan)
from repro.core.cim_dialect import (make_acquire, make_execute, make_release,
                                    make_similarity, make_yield)
from repro.core.passes import CompulsoryPartition
from repro.kernels import acam, cam_search, hdc_encode
from repro.kernels.cam_search import lane_block, row_block

N_FLAT, D_FLAT = 1_000_000, 128          # phase A: SIFT1M shape
N_CODE, BITS = 1_000_000, 256            # phase B: 256-bit codes
K, BATCH = 10, 128


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001 — skip reason
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described chip's executables cannot be read back from the
    # persistent cache, so keep it out of these compiles
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile()


def _padded(n, block):
    return -(-n // block) * block


def _flat_kernel(q, p):
    return q.unsqueeze(1).sub(p).norm(p=2, dim=-1).topk(K, largest=False)


def _flat_plan(backend):
    arch = ArchSpec(rows=256, cols=256, cam_type=CamType.MCAM,
                    bits_per_cell=8)
    return compile_fn(_flat_kernel, [(BATCH, D_FLAT), (N_FLAT, D_FLAT)], arch,
                      cam_type=CamType.MCAM, value_bits=8,
                      backend=backend).engine_plan


def _code_plan(backend):
    mod = Module("codes", [TensorType((BATCH, BITS)),
                           TensorType((N_CODE, BITS))])
    b = Builder(mod.body)
    dev = make_acquire(b)
    exe = make_execute(b, dev.result, list(mod.arguments),
                       [TensorType((BATCH, K)), TensorType((BATCH, K), "i32")])
    blk = exe.region().block()
    sim = make_similarity(blk, *mod.arguments, metric="hamming", k=K,
                          largest=False, extra_attrs={"value_bits": 1})
    make_yield(blk, sim.results)
    make_release(b, dev.result)
    b.ret(exe.results)
    pm = PassManager()
    pm.add(CompulsoryPartition())
    mod = pm.run(mod, {"arch": ArchSpec(rows=256, cols=256)})
    return get_plan(mod, backend=backend)


def _kernel_cases():
    """(id, fn, operand shapes) at the smoke's widths."""
    bn, bd = lane_block(256, N_FLAT), lane_block(128, D_FLAT)
    n_pad = _padded(N_FLAT, bn)
    lanes = BITS // 32
    hdc_f, hdc_h = 196, 10_000
    bh = lane_block(256, hdc_h)
    return [
        ("topk-eucl-k10", lambda q, p: cam_search.fused_topk_pallas(
            q, p, metric="eucl", k=K, largest=False, block_m=BATCH,
            block_n=bn, block_d=bd, n_valid=N_FLAT, interpret=False),
         [((BATCH, D_FLAT), jnp.float32), ((n_pad, D_FLAT), jnp.float32)]),
        # k not a multiple of 128 past one lane slab, one 8-row block
        ("topk-dot-k130-8rows", lambda q, p: cam_search.fused_topk_pallas(
            q, p, metric="dot", k=130, largest=True, block_m=8,
            block_n=256, block_d=384, interpret=False),
         [((8, 384), jnp.float32), ((4096, 384), jnp.float32)]),
        ("topk-packed-k10", lambda q, p: cam_search.fused_topk_packed_pallas(
            q, p, k=K, largest=False, block_m=BATCH, block_n=bn,
            block_l=lanes, n_valid=N_CODE, interpret=False),
         [((BATCH, lanes), jnp.uint32), ((lanes, n_pad), jnp.uint32)]),
        # ternary at the HDC hypervector width (313 lanes -> 128-lane blocks)
        ("topk-packed-ternary-wide",
         lambda q, p, c: cam_search.fused_topk_packed_pallas(
             q, p, c, k=K, largest=False, block_m=BATCH, block_n=256,
             block_l=128, interpret=False),
         [((BATCH, 384), jnp.uint32), ((384, 4096), jnp.uint32),
          ((384, 4096), jnp.uint32)]),
        ("acam-match", lambda q, lo, hi: acam.acam_match_pallas(
            q, lo, hi, interpret=False),
         [((BATCH, 128), jnp.float32), ((4096, 128), jnp.float32),
          ((4096, 128), jnp.float32)]),
        ("range-match-eucl", lambda q, p: acam.range_match_pallas(
            q, p, metric="eucl", threshold=3.0, interpret=False),
         [((BATCH, 512), jnp.float32), ((4096, 512), jnp.float32)]),
        ("hdc-encode", lambda q, k, lv: hdc_encode.hdc_encode_pallas(
            q, k, lv, block_m=BATCH, block_f=lane_block(256, hdc_f),
            block_h=bh, interpret=False),
         [((512, hdc_f), jnp.int32), ((hdc_f, _padded(hdc_h, bh)),
                                      jnp.float32),
          ((16, _padded(hdc_h, bh)), jnp.float32)]),
    ]


_CASES = {name: (fn, shapes) for name, fn, shapes in _kernel_cases()}


@pytest.mark.parametrize("case", list(_CASES))
def test_kernel_compiles_for_v5e(case, one_chip):
    fn, shapes = _CASES[case]
    compiled = _compile(fn, *shapes, sharding=one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("family,backend", [
    ("flat", "jnp"), ("flat", "pallas"), ("codes", "jnp"),
    ("codes", "pallas")])
def test_plan_chunk_compiles_for_v5e(family, backend, one_chip, monkeypatch):
    """The micro-batch executable a served plan runs, at the smoke's
    gallery sizes, with the prepared layout its own prepare emits."""
    # this host's backend is the CPU, so the kernels would otherwise
    # choose interpret mode; steer them to Mosaic for the described chip
    monkeypatch.setattr(cam_search, "resolve_interpret", lambda _: False)
    plan = (_flat_plan if family == "flat" else _code_plan)(backend)
    assert plan.backend == backend and plan.shards == 1
    assert plan.packed == (family == "codes")
    n, dim = (N_FLAT, D_FLAT) if family == "flat" else (N_CODE, BITS)
    dtype = jnp.float32 if family == "flat" else jnp.uint8
    stored = jax.ShapeDtypeStruct((n, dim), dtype)
    prepared = jax.eval_shape(plan._prepare, stored)
    q = jax.ShapeDtypeStruct((plan.batch, dim), dtype, sharding=one_chip)
    prepared = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        prepared)
    compiled = plan._chunk_fn.lower(q, prepared).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (backend == "pallas")
    if backend == "jnp":
        # the row tiles run in blocks, several per scan step, read in
        # place from the layout padded to whole steps, with no gather
        assert plan.tiles_per_step > 1
        tiles = plan.tiles_per_step * plan.scan_steps
        assert tiles >= plan.spec.grid_rows > tiles - plan.scan_steps
        assert all(x.shape[0] == tiles for x in prepared)
        assert "gather(" not in text
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 8 * 2 ** 30
