"""Compile the served path's device programs for a described TPU v5e chip.

Nothing runs: each test lowers and compiles one program for a v5e that is
described, not attached (the TPU compiler is part of the installed jaxlib),
at the widths of the serving schema ``chip_smoke.py`` drives (``age``,
``state``, ``income``, ``device``; device widths 8+8+8+2 bits, 58 feature
columns) over a 64K-row table; the where compaction at the size of the
TPC-H SF10 lineitem table it serves. The Pallas kernels compile with
``interpret=False``, which is what a TPU backend resolves ``None`` to; the
kernels the compiler refuses are pinned by a test that
``FeatureExecutor(use_kernel=True)`` raises with the compiler's reason.

The topology is described inside a module fixture, never at import: one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import importlib.util
import os
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import FeatureExecutor, FeaturePlan
from repro.core import pipeline
from repro.columnar import Table
from repro.kernels import KernelRefused
from repro.kernels.bitunpack.kernel import bitunpack_pallas
from repro.kernels.hist.kernel import masked_hist_pallas
from repro.kernels.predicate_scan import ops as scan_ops
from repro.kernels.predicate_scan.compact import CHUNK_ROWS

ROWS = 1 << 16
# rows of the TPC-H SF10 lineitem table the filtered pull compacts over
LINEITEM_ROWS = 59_986_052
REPO = pathlib.Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep it out of the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def plan():
    cs = _chip_smoke()
    raw = cs.generate(np.random.default_rng(0), ROWS)
    return cs, FeaturePlan(Table.from_data(raw), cs.features(), packed=True)


def _on(sharding, x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)


def _compile(fn, *args, **statics):
    compiled = fn.lower(*args, **statics).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def _split_programs(cs, plan, sharding):
    """(jit, args, statics) of each split-path program the service launches."""
    ex = FeatureExecutor(plan)
    dbs = tuple(plan.device_bits)
    offs = ex._word_offs
    words = _on(sharding, ex._flat_words)
    tables = tuple(_on(sharding, t) for t in ex._device_tables())
    mask = jax.ShapeDtypeStruct((ROWS,), jnp.bool_, sharding=sharding)
    # the running count as the scan leaves it for the full lineitem table
    ranks = jax.ShapeDtypeStruct(
        (-(-LINEITEM_ROWS // CHUNK_ROWS) * CHUNK_ROWS,), jnp.int32,
        sharding=sharding)
    bounds, lut, (cols, kinds, lut_offs, lut_lens) = \
        ex._compiled_pred(cs.P_AND[0])[2]
    return {
        "adv_gather_packed_rows_split": (
            pipeline._packed_split_rows,
            (words, tables,
             jax.ShapeDtypeStruct((512,), jnp.int32, sharding=sharding)),
            dict(dbs=dbs, word_offs=offs)),
        "predicate_scan_split_count": (
            scan_ops._scan_split_count,
            (words, _on(sharding, bounds), _on(sharding, lut)),
            dict(word_offs=offs, dbs=dbs, n=ROWS, cols=cols, kinds=kinds,
                 lut_offs=lut_offs, lut_lens=lut_lens, combine="and")),
        "predicate_scan_split_ranks": (
            scan_ops._scan_split_ranks,
            (words, _on(sharding, bounds), _on(sharding, lut)),
            dict(word_offs=offs, dbs=dbs, n=ROWS, cols=cols, kinds=kinds,
                 lut_offs=lut_offs, lut_lens=lut_lens, combine="and")),
        "_where_compact": (
            pipeline._where_compact,
            ((ranks,) * 4,
             jax.ShapeDtypeStruct((4,), jnp.int32, sharding=sharding)),
            dict(lane=pipeline.WHERE_LANE)),
        "masked_counts_split": (
            scan_ops._masked_counts_split, (words, mask),
            dict(off=offs[1], db=dbs[1], n=ROWS,
                 k=plan.augmented["state"].dictionary.cardinality)),
    }


@pytest.mark.parametrize("name", ["adv_gather_packed_rows_split",
                                  "predicate_scan_split_count",
                                  "predicate_scan_split_ranks",
                                  "_where_compact",
                                  "masked_counts_split"])
def test_split_path_compiles_for_v5e(name, one_chip, plan):
    cs, p = plan
    fn, args, statics = _split_programs(cs, p, one_chip)[name]
    compiled = _compile(fn, *args, **statics)
    if name == "_where_compact":
        # a TPU walks each lane's span in the compaction kernel
        assert compiled.as_text().count("tpu_custom_call") >= 4


def test_masked_hist_kernel_compiles_for_v5e(one_chip, plan):
    _, p = plan
    k = p.augmented["state"].dictionary.cardinality
    codes = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((ROWS,), jnp.int32, sharding=one_chip)
    compiled = _compile(masked_hist_pallas, codes, mask, k=512,
                        interpret=False)
    assert k <= 512
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("bits", [2, 8])
def test_bitunpack_kernel_compiles_for_v5e(bits, one_chip):
    words = jax.ShapeDtypeStruct((ROWS * bits // 32,), jnp.uint32,
                                 sharding=one_chip)
    compiled = _compile(bitunpack_pallas, words, bits=bits, interpret=False)
    assert "tpu_custom_call" in compiled.as_text()


def test_kernel_executor_refuses_uncompilable_kernels(topo, one_chip, plan):
    """The rows kernel and the predicate scan gather in-kernel with
    ``jnp.take``, which Mosaic does not lower: a kernel executor for a TPU
    says so, naming each kernel with the compiler's reason, instead of
    interpreting or serving from the host."""
    _, p = plan
    with pytest.raises(KernelRefused) as err:
        FeatureExecutor(p, use_kernel=True, device=topo.devices[0],
                        commit=False)
    refused = err.value.refused
    assert set(refused) == {"adv_gather_packed_rows", "predicate_scan"}
    assert all(why.strip() for why in refused.values())
    for name in refused:
        assert f"{name} does not compile for this device" in str(err.value)
    # the split executor for the same device builds without compiling
    FeatureExecutor(p, device=topo.devices[0], commit=False)
