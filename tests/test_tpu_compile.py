"""Compile the main path's kernels for a TPU v5e at real widths.

Nothing runs: each test compiles for a described (not attached) v5e chip,
which refuses what interpret mode accepts — lane slices Mosaic cannot prove
128-aligned, kernels that overflow scoped VMEM, programs that overflow HBM.
Kernels are compiled with ``interpret=False`` and must appear as a Mosaic
``tpu_custom_call``, the search kernels under their own names (what a
profiler trace shows); every program must fit one chip's 16 GiB.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.hd.encoding import encode_levels_batch
from repro.core.hd.similarity import bitpack_bipolar
from repro.kernels.encode_search import (
    encode_search_banded_pallas,
    encode_search_pallas,
)
from repro.kernels.hamming_pop import hamming_pop_pallas
from repro.kernels.hd_encode import hd_encode_pallas
from repro.kernels.topk_hamming import (
    topk_hamming_banded_pallas,
    topk_hamming_pallas,
)

HBM_BYTES = 16 * 2**30
BANK_ROWS = 2 * 1_162_392  # iPRG2012 targets + decoys
Q, K, F, LEVELS = 128, 4, 1024, 16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # described-topology compiles can be written to the persistent cache
    # but never read back without a chip: keep them out of it
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # pragma: no cover - depends on the install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used <= HBM_BYTES, f"{used} bytes exceed one chip"
    return compiled.as_text(), mem


def _named(text: str, kernel: str) -> bool:
    """The program holds a Mosaic kernel named ``kernel``."""
    return re.search(rf'%{kernel}(\.\d+)? = .*"tpu_custom_call"',
                     text) is not None


@pytest.mark.parametrize("dim", [8192, 2048])
def test_packed_topk_compiles(one_chip, dim):
    w = dim // 32
    text, mem = _compile(
        lambda q, r: topk_hamming_pallas(q, r, dim=dim, k=K, interpret=False),
        one_chip, ((Q, w), jnp.uint32), ((BANK_ROWS, w), jnp.uint32))
    assert "tpu_custom_call" in text
    assert _named(text, "topk_hamming")
    if dim == 8192:  # lane-aligned words: the bank is read in place
        assert mem.temp_size_in_bytes < 2**20


def test_int8_topk_compiles(one_chip):
    dim = 8192
    text, _ = _compile(
        lambda q, r: topk_hamming_pallas(q, r, dim=dim, k=K, interpret=False),
        one_chip, ((Q, dim), jnp.int8), ((262_144, dim), jnp.int8))
    assert "tpu_custom_call" in text
    assert _named(text, "topk_hamming")


@pytest.mark.parametrize("dim", [8192, 2048])
def test_banded_topk_compiles(one_chip, dim):
    w = dim // 32
    text, _ = _compile(
        lambda q, r, s, n: topk_hamming_banded_pallas(
            q, r, s, n, dim=dim, k=K, num_tiles=1024, block_q=8,
            interpret=False),
        one_chip, ((Q, w), jnp.uint32), ((BANK_ROWS, w), jnp.uint32),
        ((Q,), jnp.int32), ((Q,), jnp.int32))
    assert "tpu_custom_call" in text
    assert _named(text, "topk_hamming_banded")


@pytest.mark.parametrize("dim", [8192, 2048])
def test_hamming_pop_compiles(one_chip, dim):
    w = dim // 32
    text, _ = _compile(
        lambda q, r: hamming_pop_pallas(q, r, dim=dim, interpret=False),
        one_chip, ((Q, w), jnp.uint32), ((4096, w), jnp.uint32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dim,block_q", [(8192, 8), (8192, 128), (2048, 8)])
def test_packed_encode_search_compiles(one_chip, dim, block_q):
    w = dim // 32
    text, _ = _compile(
        lambda lv, ids, lvs, r: encode_search_pallas(
            lv, ids, lvs, r, dim=dim, k=K, block_q=block_q,
            interpret=False),
        one_chip, ((Q, F), jnp.int32), ((F, dim), jnp.int8),
        ((LEVELS, dim), jnp.int8), ((BANK_ROWS, w), jnp.uint32))
    assert "tpu_custom_call" in text
    assert _named(text, "encode_search")


def test_int8_encode_search_compiles(one_chip):
    dim = 8192
    text, _ = _compile(
        lambda lv, ids, lvs, r: encode_search_pallas(
            lv, ids, lvs, r, dim=dim, k=K, interpret=False),
        one_chip, ((Q, F), jnp.int32), ((F, dim), jnp.int8),
        ((LEVELS, dim), jnp.int8), ((65_536, dim), jnp.int8))
    assert "tpu_custom_call" in text
    assert _named(text, "encode_search")


def test_banded_encode_search_compiles(one_chip):
    dim = 8192
    text, _ = _compile(
        lambda lv, ids, lvs, r, s, n: encode_search_banded_pallas(
            lv, ids, lvs, r, s, n, dim=dim, k=K, num_tiles=1024,
            interpret=False),
        one_chip, ((Q, F), jnp.int32), ((F, dim), jnp.int8),
        ((LEVELS, dim), jnp.int8), ((BANK_ROWS, dim // 32), jnp.uint32),
        ((Q,), jnp.int32), ((Q,), jnp.int32))
    assert "tpu_custom_call" in text
    assert _named(text, "encode_search_banded")


def test_hd_encode_compiles(one_chip):
    dim = 8192
    text, _ = _compile(
        lambda lv, ids, lvs: hd_encode_pallas(lv, ids, lvs, interpret=False),
        one_chip, ((Q, F), jnp.int32), ((F, dim), jnp.int8),
        ((LEVELS, dim), jnp.int8))
    assert "tpu_custom_call" in text


def test_bank_encoder_chunk_compiles(one_chip):
    """One chunk of the library encoder: its working set is the chunk's
    (rows, D) accumulator, not a (rows, F, D) gather."""
    dim, rows = 8192, 8192
    _, mem = _compile(
        lambda lv, ids, lvs: bitpack_bipolar(
            encode_levels_batch(lv, ids, lvs)),
        one_chip, ((rows, F), jnp.int32), ((F, dim), jnp.int8),
        ((LEVELS, dim), jnp.int8))
    assert mem.output_size_in_bytes == rows * dim // 8
    assert mem.temp_size_in_bytes < 2**30


def test_served_oms_batch_program_compiles(one_chip, monkeypatch):
    """The served OMS batch as one program at the HEK293 cell's size
    (4,489,008 packed rows, a 2,048-tile budget, two bands): the banded
    kernel, the overflow tail, the permutation gather, the unsort and FDR
    compile together and fit one chip."""
    from repro.kernels.encode_search import ops
    from repro.serve import db_search
    # the program picks interpret mode from the backend, which is the CPU
    # here: compile the Mosaic kernel the chip runs instead
    monkeypatch.setattr(ops, "default_interpret", lambda: False)
    dim, rows = 8192, 4_489_008
    geometry = db_search._geometry(db_search.ShardedDatabase(
        data=None, num_rows=rows, num_decoys=rows // 2, dim=dim,
        shard_rows=rows, packed=True, mesh=None, axis="model", fused=True))
    text, _ = _compile(
        lambda lv, ids, lvs, s, n_, r, perm, inv, hc, n:
        db_search._oms_batch_program(
            lv, ids, lvs, s, n_, r, perm, inv, hc, n, geometry=geometry,
            k=K, num_tiles=2048, fdr=0.01),
        one_chip, ((Q, F), jnp.int32), ((F, dim), jnp.int8),
        ((LEVELS, dim), jnp.int8), ((2, Q), jnp.int32), ((2, Q), jnp.int32),
        ((rows, dim // 32), jnp.uint32), ((rows,), jnp.int32),
        ((Q,), jnp.int32), ((Q,), jnp.bool_), ((), jnp.int32))
    assert _named(text, "encode_search_banded")
