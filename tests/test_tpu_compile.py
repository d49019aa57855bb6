"""Compile-only checks for the TPU v5e: the main path's programs and its
Pallas kernels at real widths, compiled for a described ``v5e:2x2`` topology
with no chip attached.

Nothing runs, so these say nothing about results or times.  They prove that
the chip's compiler accepts each program (tiling rules, VMEM limits,
partitioning) and that a one-chip program fits the chip's 16 GB of HBM.

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every pytest-xdist
worker imports every test file.  Keep all such compiles in this one file so
that they land on one worker.
"""
from __future__ import annotations

import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16e9

# MLI logistic regression at one chip's share of the paper's §IV-A run
LOGREG_ROWS, LOGREG_COLS = 6_144, 160_001      # label + 160,000 features
LOGREG_PARTITIONS, LOGREG_BATCH, LOGREG_ROUNDS = 4, 32, 2


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip can be written to the persistent cache
    # but never read back without one: keep the cache out of these compiles
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        with pytest.MonkeyPatch.context() as mp:
            # the TPU compiler otherwise writes its logs under /tmp
            mp.setenv("TPU_LOG_DIR",
                      os.environ.get("TPU_LOG_DIR", "disabled"))
            try:
                desc = topologies.get_topology_desc(platform="tpu",
                                                    topology_name="v5e:2x2")
            except Exception as e:  # no TPU compiler in this installation
                pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
            yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                sharding=sharding)


def _footprint(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


def _logreg_local_round():
    from repro.core.algorithms.logistic_regression import (
        LogisticRegressionParameters, _make_gradient)
    from repro.core.optimizer import (StochasticGradientDescent,
                                      StochasticGradientDescentParameters)

    d = LOGREG_COLS - 1
    p = StochasticGradientDescentParameters(
        w_init=np.zeros(d, np.float32),
        grad=_make_gradient(LogisticRegressionParameters()),
        learning_rate=64.0 / d, max_iter=LOGREG_ROUNDS,
        local_batch_size=LOGREG_BATCH)
    return StochasticGradientDescent._local_round(p)


def test_logreg_round_program_fits_one_chip(one_chip):
    """The resident Fig. A4 program of ``LogisticRegressionAlgorithm.fit``
    (four emulated partitions, carry donated as on the chip) at 6,144 x
    160,001 f32 compiles and fits one chip's HBM."""
    from repro.core.runner import DistributedRunner

    runner = DistributedRunner(num_shards=LOGREG_PARTITIONS, donate=True)
    run = runner.rounds_fn(_logreg_local_round(), LOGREG_ROUNDS)
    compiled = run.lower(
        _sds((LOGREG_COLS - 1,), np.float32, one_chip),
        _sds((LOGREG_ROWS, LOGREG_COLS), np.float32, one_chip)).compile()
    table_bytes = LOGREG_ROWS * LOGREG_COLS * 4
    assert compiled.memory_analysis().argument_size_in_bytes >= table_bytes
    assert _footprint(compiled) < V5E_HBM_BYTES


_HLO_OP = re.compile(r"= (\w+\[[\d,]*\])(\{[^}]*\})? ([\w-]+)\(")


def _minibatch_ops(hlo: str):
    """The ops of a compiled logreg round program that touch one minibatch:
    (result shape, layout) of each copy of a 32-row slice of the table, and
    the result shapes of the reductions the gradient makes of it."""
    copies, reductions = [], set()
    for line in hlo.splitlines():
        m = _HLO_OP.search(line)
        if m is None:
            continue
        shape, layout, opcode = m.groups()
        name = line.split("=", 1)[0]
        if opcode == "copy" and shape.endswith(f"{LOGREG_BATCH},{LOGREG_COLS}]"):
            copies.append((shape, layout))
        elif opcode == "fusion" and "reduce_fusion" in name:
            reductions.add(shape)
    return sorted(copies), reductions


def test_logreg_emulated_partitions_compile_as_mesh_chips(topo, one_chip):
    """Four emulated partitions on one chip run the program one chip of the
    4-chip mesh runs, once per partition: no op of a minibatch carries a
    partition dimension (a vmap over partitions made XLA transpose every
    (4, 32, 160,001) minibatch in HBM), the two reductions of each step
    make f32[32] margins and an f32[160000] gradient, every copy of a
    minibatch is one the mesh program makes too, and the program still
    fits one chip."""
    from repro.core.runner import DistributedRunner

    local_round = _logreg_local_round()
    emulated = DistributedRunner(num_shards=LOGREG_PARTITIONS, donate=True)
    compiled = emulated.rounds_fn(local_round, LOGREG_ROUNDS).lower(
        _sds((LOGREG_COLS - 1,), np.float32, one_chip),
        _sds((LOGREG_ROWS, LOGREG_COLS), np.float32, one_chip)).compile()
    assert _footprint(compiled) < V5E_HBM_BYTES
    hlo = compiled.as_text()
    assert f"{LOGREG_PARTITIONS},{LOGREG_BATCH},{LOGREG_COLS}]" not in hlo

    mesh = Mesh(np.asarray(topo.devices), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))
    meshed = DistributedRunner(mesh=mesh, donate=True)
    mesh_hlo = meshed.rounds_fn(local_round, LOGREG_ROUNDS).lower(
        _sds((LOGREG_COLS - 1,), np.float32, NamedSharding(mesh, P())),
        _sds((LOGREG_ROWS * len(topo.devices), LOGREG_COLS), np.float32,
             NamedSharding(mesh, P("data", None)))).compile().as_text()

    copies, reductions = _minibatch_ops(hlo)
    assert reductions == {f"f32[{LOGREG_BATCH}]", f"f32[{LOGREG_COLS - 1}]"}
    assert (copies, reductions) == _minibatch_ops(mesh_hlo)


@pytest.mark.parametrize("schedule", ["gather_broadcast", "allreduce",
                                      "reduce_scatter"])
def test_logreg_round_program_on_four_chip_mesh(topo, schedule):
    """Weak scaling on the 2x2 host: 6,144 rows per chip on a ("data",)
    mesh.  Each chip holds only its own rows, and the per-round weight
    average lowers to cross-chip collectives under every schedule."""
    from repro.core.runner import DistributedRunner

    mesh = Mesh(np.asarray(topo.devices), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))
    runner = DistributedRunner(mesh=mesh, schedule=schedule, donate=True)
    run = runner.rounds_fn(_logreg_local_round(), LOGREG_ROUNDS)
    rows = LOGREG_ROWS * len(topo.devices)
    compiled = run.lower(
        _sds((LOGREG_COLS - 1,), np.float32, NamedSharding(mesh, P())),
        _sds((rows, LOGREG_COLS), np.float32,
             NamedSharding(mesh, P("data", None)))).compile()
    per_chip_table = LOGREG_ROWS * LOGREG_COLS * 4
    args = compiled.memory_analysis().argument_size_in_bytes
    assert per_chip_table <= args < 2 * per_chip_table
    assert _footprint(compiled) < V5E_HBM_BYTES
    hlo = compiled.as_text()
    assert any(op in hlo for op in ("all-reduce", "all-gather",
                                    "reduce-scatter"))


def _kernel_case(name, one_chip):
    """(jitted kernel, argument shapes) at the widths the main path and the
    configs use."""
    from repro.kernels.flash_attention import flash_attention
    from repro.kernels.kmeans_assign import kmeans_assign_pallas
    from repro.kernels.logreg_grad import logreg_grad_pallas
    from repro.kernels.quant_matmul import quant_matmul_pallas
    from repro.kernels.rmsnorm import rmsnorm_pallas
    from repro.kernels.ssd_scan import ssd_chunk_scan

    s = lambda shape, dtype: _sds(shape, dtype, one_chip)  # noqa: E731
    if name == "logreg_grad":
        # the paper's 160,000 features, zero-padded to the 512-wide block
        d = 160_256
        return jax.jit(logreg_grad_pallas), (
            s((2_048, d), np.float32), s((2_048,), np.float32),
            s((d,), np.float32))
    if name == "kmeans_assign":
        return kmeans_assign_pallas, (s((8_192, 1_024), np.float32),
                                      s((16, 1_024), np.float32))
    if name == "flash_attention":   # qwen2-1.5b: 12 heads, 2 KV heads
        return flash_attention, (s((1, 12, 2_048, 128), jnp.bfloat16),
                                 s((1, 2, 2_048, 128), jnp.bfloat16),
                                 s((1, 2, 2_048, 128), jnp.bfloat16))
    if name == "rmsnorm":
        return rmsnorm_pallas, (s((4_096, 1_536), jnp.bfloat16),
                                s((1_536,), jnp.bfloat16))
    if name == "quant_matmul":      # qwen2-1.5b MLP up-projection
        return quant_matmul_pallas, (
            s((256, 1_536), np.int8), s((256,), np.float32),
            s((1_536, 8_960), np.int8), s((8_960,), np.float32))
    if name == "ssd_chunk_scan":    # mamba2-2.7b: 80 heads, P=64, N=128
        B, H, S, Pd, N = 1, 80, 2_048, 64, 128
        return ssd_chunk_scan, (
            s((B, H, S), np.float32), s((B, H, S, Pd), np.float32),
            s((B, S, N), np.float32), s((B, S, N), np.float32))
    raise KeyError(name)


@pytest.mark.parametrize("name", ["logreg_grad", "kmeans_assign",
                                  "flash_attention", "rmsnorm",
                                  "quant_matmul", "ssd_chunk_scan"])
def test_pallas_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_case(name, one_chip)
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _serving_args(cfg, slots, max_seq, one_chip):
    """(model, parameter shapes, cache shapes) of ``cfg`` on one chip."""
    from repro.models.transformer import TransformerLM, init_model

    model = TransformerLM(cfg)
    on_chip = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: _sds(a.shape, a.dtype, one_chip), tree)
    params = on_chip(jax.eval_shape(lambda k: init_model(k, cfg)[0],
                                    jax.random.PRNGKey(0)))
    cache = on_chip(jax.eval_shape(lambda: model.init_cache(slots, max_seq)))
    return model, params, cache


def _compiled_decode(cfg, slots, max_seq, one_chip):
    model, params, cache = _serving_args(cfg, slots, max_seq, one_chip)
    fn = jax.jit(lambda p, t, pos, c: model.decode_step(p, t, pos, c))
    return fn.lower(params, _sds((slots, 1), np.int32, one_chip),
                    _sds((slots,), np.int32, one_chip), cache).compile()


@pytest.mark.parametrize("program", ["prefill_ragged", "decode_step"])
def test_qwen2_serving_program_fits_one_chip(one_chip, program):
    """qwen2-1.5b at its published widths, as ``ServeEngine`` jits it for 4
    slots and a 2,048-token cache: a 1,024-token ragged prefill wave and the
    fused decode step compile and fit one chip's HBM."""
    from repro.configs import get_config

    cfg = get_config("qwen2-1.5b")
    slots, max_seq = 4, 2_048
    if program == "prefill_ragged":
        model, params, cache = _serving_args(cfg, slots, max_seq, one_chip)
        lens = _sds((slots,), np.int32, one_chip)
        fn = jax.jit(lambda p, t, n, c: model.prefill_ragged(p, t, n, c))
        compiled = fn.lower(params, _sds((slots, 1_024), np.int32, one_chip),
                            lens, cache).compile()
    else:
        compiled = _compiled_decode(cfg, slots, max_seq, one_chip)
    assert _footprint(compiled) < V5E_HBM_BYTES


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "granite-4.0-h-micro"])
def test_decode_step_reads_kv_at_kv_head_width(one_chip, arch):
    """The decode step of the serving cells (32 slots x 1,024 positions)
    contracts the queries against K/V at their own kv heads: no op of the
    compiled program outputs a cache-sized tensor at query-head width
    (slots x positions x query heads x head size, in any order, unit axes
    aside), as K/V repeated to every query head would; and it fits one
    chip."""
    from repro.configs import get_config
    from repro.configs.granite_4_0_h_micro import FULL as GRANITE

    cfg = GRANITE if arch == "granite-4.0-h-micro" else get_config(arch)
    slots, max_seq = 32, 1_024
    compiled = _compiled_decode(cfg, slots, max_seq, one_chip)
    assert _footprint(compiled) < V5E_HBM_BYTES

    wide = sorted([slots, max_seq, cfg.num_heads, cfg.resolved_head_dim])
    at_kv = sorted([slots, max_seq, cfg.num_kv_heads, cfg.resolved_head_dim])
    shapes = []
    for line in compiled.as_text().splitlines():
        m = _HLO_OP.search(line)
        if m is not None:
            dims = m.group(1).split("[", 1)[1].rstrip("]").split(",")
            shapes.append(sorted(int(d) for d in dims if d and d != "1"))
    assert at_kv in shapes           # the cache itself is read per layer
    assert wide not in shapes


@pytest.mark.parametrize("name", ["logreg_grad", "kmeans_assign",
                                  "quant_matmul"])
def test_ops_wrapper_pads_untileable_shapes_for_v5e(one_chip, monkeypatch,
                                                    name):
    """The wrappers' TPU branch at shapes the blocks do not tile (the
    paper's 160,000 features; a 1,000-wide k-means table; a vocab-sized
    int8 projection) pads to the tile and still compiles to the kernel."""
    from repro.kernels import ops

    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    s = lambda shape, dtype: _sds(shape, dtype, one_chip)  # noqa: E731
    if name == "logreg_grad":
        fn, args = ops.logreg_grad, (s((2_048, 160_000), np.float32),
                                     s((2_048,), np.float32),
                                     s((160_000,), np.float32))
    elif name == "kmeans_assign":
        fn, args = ops.kmeans_assign, (s((1_000, 1_000), np.float32),
                                       s((16, 1_000), np.float32))
    else:
        fn, args = ops.quant_matmul, (s((4, 1_536), np.int8),
                                      s((4,), np.float32),
                                      s((1_536, 151_936), np.int8),
                                      s((151_936,), np.float32))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
