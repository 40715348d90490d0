"""The serving path's device programs compile for a TPU v5e at real size.

No chip is attached here: the TPU compiler is installed and compiles for a
chip that is DESCRIBED (``jax.experimental.topologies``), which refuses what
the chip's compiler would refuse — a kernel over its scoped VMEM, a program
over 16 GB of HBM — at no chip time. Nothing runs, so nothing here says
anything about answers or speed; ``chip_smoke.py`` is the proof on silicon.

The topology is described inside a module-scoped fixture (never at import:
only one process may load libtpu, and every xdist worker imports this file),
the compiles run in the test's own process with the persistent cache off (a
described-device executable cannot be read back from it), and every such
test lives in THIS file so one worker owns the library.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

HBM_BYTES = 16 * 10 ** 9   # one v5e chip
N, D, B, K = 1 << 20, 768, 256, 10
CHUNK = 131072             # FlatIndexConfig.search_chunk_size default
# graph-walk shapes (ISSUE 22 finding 1)
GD, M0, EF, MAX_STEPS = 128, 64, 100, 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # whatever libtpu raises where it cannot load
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _fits(compiled) -> int:
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes + m.generated_code_size_in_bytes)
    assert 0 < total < HBM_BYTES, m
    return total


def _flat_args(sharding):
    s = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    # the rows as a default flat collection keeps them: bfloat16
    return (s((B, D), jnp.float32), s((N, D), jnp.bfloat16),
            s((N,), jnp.bool_), s((N,), jnp.float32))


@pytest.mark.parametrize("approx_recall", [0.0, 0.95],
                         ids=["exact", "approx"])
@pytest.mark.parametrize("metric", ["l2-squared", "cosine"])
def test_flat_search_compiles_at_serving_defaults(one_chip, metric,
                                                  approx_recall):
    """The program a default FlatIndexConfig collection serves with: bf16
    matmul, exact selection, 131072-row chunks over a 1M x 768 bfloat16 corpus;
    and the one a collection with ``flat_approx_recall`` set serves with
    (``lax.approx_min_k`` a chunk), the only approximate flat program.
    Cosine as ``FlatIndex`` asks for it: the scan normalises its queries."""
    from weaviate_tpu.ops.distance import flat_search

    q, corpus, valid, sqnorms = _flat_args(one_chip)
    compiled = flat_search.lower(
        q, corpus, k=K, metric=metric, valid_mask=valid,
        corpus_sqnorms=sqnorms if metric == "l2-squared" else None,
        chunk_size=CHUNK, precision="bf16",
        approx_recall=approx_recall,
        normalize_queries=metric == "cosine").compile()
    # the corpus is an argument, not a temporary: >= 1.6 GB resident
    assert compiled.memory_analysis().argument_size_in_bytes >= N * D * 2
    _fits(compiled)


@pytest.mark.parametrize("rows", [4, 8])
def test_flat_search_compiles_with_a_mask_a_row(one_chip, rows):
    """What a flat collection runs for a group of filtered requests whose
    masks differ: a [rows, capacity] allow mask, sliced chunk by chunk
    (``yfcc192.filtered_c20``: 524,288 x 192, l2-squared, k = 10)."""
    from weaviate_tpu.ops.distance import flat_search

    cap, dims = 524288, 192
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = flat_search.lower(
        s((rows, dims), jnp.float32), s((cap, dims), jnp.bfloat16), k=K,
        metric="l2-squared", valid_mask=s((cap,), jnp.bool_),
        allow_mask=s((rows, cap), jnp.bool_),
        corpus_sqnorms=s((cap,), jnp.float32), chunk_size=CHUNK,
        precision="bf16", approx_recall=0.0).compile()
    assert compiled.memory_analysis().argument_size_in_bytes >= \
        cap * dims * 2 + rows * cap
    _fits(compiled)


def _whole_corpus_ops(compiled, cap: int, dims: int) -> list[str]:
    """Names of the instructions that convert or copy a ``[cap, dims]``
    array: a pass over the whole corpus before (or in place of) the scan."""
    shape = re.compile(rf"%(\S+) = \w+\[{cap},{dims}\]\S* (convert|copy)\(")
    return [m.group(1) for m in map(shape.search,
                                    compiled.as_text().splitlines()) if m]


# the four flat shapes the benchmark's cells run (capacity, D, metric, B,
# a mask a row): cohere-768-flat / msmarco-768-hybrid alone and in a batch,
# yfcc-192-filtered with stacked masks, sift-128-flat
CELL_SHAPES = [
    pytest.param(262144, 768, "cosine", 1, False, id="768-cosine-b1"),
    pytest.param(262144, 768, "cosine", 8, False, id="768-cosine-b8"),
    pytest.param(524288, 192, "l2-squared", 8, True, id="192-l2-masks-b8"),
    pytest.param(262144, 128, "l2-squared", 4, False, id="128-l2-b4"),
]


@pytest.mark.parametrize("cap,dims,metric,rows,masked", CELL_SHAPES)
def test_flat_search_over_bfloat16_rows_has_no_whole_corpus_pass(
        one_chip, cap, dims, metric, rows, masked):
    """A flat collection's rows are resident in bfloat16 (``index/flat.py
    resident_dtype``), so ``_matmul``'s cast of the corpus is the identity:
    the compiled scan neither converts nor re-lays-out the corpus and keeps
    no copy of it. Over float32 rows the convert is there, so this test
    would see it come back."""
    from weaviate_tpu.ops.distance import flat_search

    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)

    def compile_over(dtype):
        return flat_search.lower(
            s((rows, dims), jnp.float32), s((cap, dims), dtype), k=K,
            metric=metric, valid_mask=s((cap,), jnp.bool_),
            allow_mask=s((rows, cap), jnp.bool_) if masked else None,
            corpus_sqnorms=(s((cap,), jnp.float32)
                            if metric == "l2-squared" else None),
            chunk_size=CHUNK, precision="bf16", approx_recall=0.0,
            normalize_queries=metric == "cosine").compile()

    narrow = compile_over(jnp.bfloat16)
    assert _whole_corpus_ops(narrow, cap, dims) == []
    m = narrow.memory_analysis()
    assert m.temp_size_in_bytes < cap * dims * 2 // 100, m
    assert cap * dims * 2 <= m.argument_size_in_bytes < cap * dims * 4
    _fits(narrow)
    wide = _whole_corpus_ops(compile_over(jnp.float32), cap, dims)
    assert any(name.startswith("convert") for name in wide), wide


@pytest.mark.parametrize("rows", [1, 4, 8])
def test_rq_scan_compiles_at_every_row_bucket(one_chip, rows):
    """The code scan a compressed flat collection runs for a batch of each
    row bucket (``index/flat.py ROW_BUCKETS``) at ``cohere768.rq_c20``'s
    shape: 262,144 x 768 uint8 RQ codes, cosine, the top 40 in 131,072-row
    chunks. The codes are read where they lie: the program keeps no
    temporary of a chunk's size, whatever the batch."""
    from weaviate_tpu.ops.quantized import rq_search

    cap, dims = 262144, 768
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = rq_search.lower(
        s((rows, dims), jnp.float32), s((cap, dims), jnp.uint8),
        s((cap,), jnp.float32), s((cap,), jnp.float32),
        s((cap,), jnp.float32), s((cap,), jnp.bool_), metric="cosine",
        k=40, chunk=CHUNK).compile()
    m = compiled.memory_analysis()
    assert m.temp_size_in_bytes < CHUNK * dims // 100, m
    assert m.argument_size_in_bytes >= cap * dims
    _fits(compiled)


def _graph_args(sharding):
    s = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    levels, slots, m_upper = 3, N // 32, M0 // 2
    return dict(
        queries=s((B, GD), jnp.float32),
        adjacency=s((N, M0), jnp.int32),
        present=s((N,), jnp.bool_),
        eps=s((B,), jnp.int32),
        upper_adj=s((levels, slots, m_upper), jnp.int32),
        upper_slots=s((levels, N), jnp.int32),
    ), s


@pytest.mark.parametrize("backend", ["raw", "sq"])
def test_fused_graph_walk_compiles(one_chip, backend):
    """The one-dispatch HNSW walk (descent + layer-0 beam) over a 1M-node
    graph, on the raw corpus and on SQ code planes."""
    from weaviate_tpu.ops.device_beam import RawScorer, SQScorer, \
        _fused_search

    g, s = _graph_args(one_chip)
    if backend == "raw":
        scorer = RawScorer("l2-squared", "bf16")
        operands = (s((N, GD), jnp.float32),)
    else:
        scorer = SQScorer("l2-squared")
        operands = (s((N, GD), jnp.uint8), s((N,), jnp.float32),
                    s((), jnp.float32), s((), jnp.float32))
    compiled = _fused_search.lower(
        scorer, g["queries"], operands, g["adjacency"], g["present"],
        g["eps"], g["upper_adj"], g["upper_slots"], ef=EF,
        max_steps=MAX_STEPS).compile()
    _fits(compiled)


def test_gather_distance_compiles(one_chip):
    """The host-driven walk's per-hop program: [256, 256] candidate ids
    gathered from and scored against a 1M x 128 corpus."""
    from weaviate_tpu.ops.distance import gather_distance

    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = jax.jit(
        gather_distance, static_argnames=("metric", "precision")).lower(
        s((B, GD), jnp.float32), s((N, GD), jnp.float32),
        s((B, 256), jnp.int32), metric="l2-squared",
        precision="bf16").compile()
    _fits(compiled)


def test_mesh_flat_search_compiles_on_four_chips(topo):
    """``mesh_flat_topk``'s program on the 2x2 host: corpus rows sharded
    over four chips, per-chip chunked scan, all_gather top-k merge."""
    from weaviate_tpu.parallel.mesh import SHARD_AXIS
    from weaviate_tpu.parallel.sharded_search import _sharded_flat_search_jit

    mesh = Mesh(np.array(topo.devices[:4]), (SHARD_AXIS,))
    row = NamedSharding(mesh, P(SHARD_AXIS, None))
    flat = NamedSharding(mesh, P(SHARD_AXIS))
    repl = NamedSharding(mesh, P(None, None))
    compiled = _sharded_flat_search_jit.lower(
        jax.ShapeDtypeStruct((N, D), jnp.bfloat16, sharding=row),
        jax.ShapeDtypeStruct((N,), jnp.bool_, sharding=flat),
        jax.ShapeDtypeStruct((B, D), jnp.float32, sharding=repl),
        k=K, metric="l2-squared", mesh=mesh, precision="bf16",
        sqnorms=jax.ShapeDtypeStruct((N,), jnp.float32, sharding=flat),
        chunk_size=CHUNK, approx_recall=0.0).compile()
    # memory_analysis is per device: each chip holds a quarter of the rows
    per_chip = compiled.memory_analysis().argument_size_in_bytes
    assert N * D * 2 // 4 <= per_chip < N * D * 2 // 2
    assert "all-gather" in compiled.as_text()
    _fits(compiled)


@pytest.mark.parametrize("candidates", [256, 1024])
def test_fused_multivector_search_compiles_at_the_cell_s_size(one_chip,
                                                              candidates):
    """``msmarco128.multivector_c20``'s one program a request: the scan of a
    65,536 x 2,560 float32 FDE plane, the gather of the candidates' token
    sets from [65,536, 192, 128] bfloat16 planes, their exact MaxSim with a
    [32, 128] query and the top-k. The planes are arguments (3.9 GB), the
    gathered sets and the [C, Tq, Td] products temporaries."""
    from weaviate_tpu.modules.device import MaxSimRerank
    from weaviate_tpu.modules.device.store import TOKEN_DTYPE
    from weaviate_tpu.ops.device_beam import _fused_flat_rerank

    cap, fde, tokens, dims, tq = 65536, 2560, 192, 128, 32
    s = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    compiled = _fused_flat_rerank.lower(
        MaxSimRerank(), s((1, fde), jnp.float32), s((cap, fde), jnp.float32),
        s((cap,), jnp.bool_), s((1, tq, dims), jnp.float32),
        s((1, tq), jnp.bool_), s((cap, tokens, dims), TOKEN_DTYPE),
        s((cap, tokens), jnp.bool_), fetch=candidates, k=16, metric="dot",
        precision="bf16").compile()
    assert compiled.memory_analysis().argument_size_in_bytes >= \
        cap * fde * 4 + cap * tokens * dims * 2
    _fits(compiled)
