"""Compile-only guards at the served widths for a described TPU v5e.

Interpret mode cannot see what the TPU compiler refuses (block shapes off
the (8, 128) tiling, unaligned DMA slices, VMEM overruns) nor what a
program would need in HBM.  These tests compile every Pallas kernel of
the served path natively — and the served exact-search program once — at
the widths ``chip_smoke.py`` runs (1,000,000 x 128 rows, 4 attributes,
PQ m=16 / ks=256, nlist 1024) for a ``v5e:2x2`` topology described
without a chip.  Nothing runs; a refusal raises at ``compile()``.

The topology is described inside a module fixture, never at import, so
every test worker collects the same tests and only the worker that runs
this file loads the TPU compiler.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import filter_distance, ivf_score, pq_score, visit_step

N, D, A, T, V, B = 1_000_000, 128, 4, 4, 64, 32
PQ_M, PQ_KS, NLIST, CAP = 16, 256, 1024, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to guard
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    # a described-chip compile is written to the persistent cache but can
    # never be read back without the chip: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("with_live", [False, True])
def test_visit_step_compiles(one_chip, with_live):
    s = lambda *a: _spec(one_chip, *a)
    live = s((N + 1,), jnp.bool_) if with_live else None

    def fn(vectors, attrs, live, idx, mask, q, lo, hi):
        # vmapped per query, as the engine calls it
        return jax.vmap(lambda i, m, q1: visit_step.visit_step(
            vectors, attrs, live, i, m, q1, lo, hi, interpret=False))(idx, mask, q)

    _compile(fn, s((N + 1, D)), s((N + 1, A)), live, s((B, V), jnp.int32),
             s((B, V), jnp.bool_), s((B, D)), s((T, A)), s((T, A)))


def test_filter_distance_batch_compiles(one_chip):
    s = lambda *a: _spec(one_chip, *a)
    _compile(
        lambda *z: filter_distance.filter_distance_batch(*z, interpret=False),
        s((N + 1, D)), s((N + 1, A)), s((B, CAP), jnp.int32), s((B, CAP), jnp.bool_),
        s((B, D)), s((B, T, A)), s((B, T, A)),
    )


def test_ivf_score_compiles(one_chip):
    s = lambda *a: _spec(one_chip, *a)
    _compile(lambda q, c: ivf_score.ivf_score(q, c, interpret=False),
             s((B, D)), s((NLIST, D)))


def test_pq_score_compiles(one_chip):
    s = lambda *a: _spec(one_chip, *a)

    def fn(codes, attrs, idx, mask, luts, lo, hi):
        return jax.vmap(lambda i, m, t: pq_score.pq_score(
            codes, attrs, i, m, t, lo, hi, interpret=False))(idx, mask, luts)

    _compile(fn, s((N + 1, PQ_M), jnp.uint8), s((N + 1, A)), s((B, V), jnp.int32),
             s((B, V), jnp.bool_), s((B, PQ_M, PQ_KS)), s((T, A)), s((T, A)))


def test_pq_score_batch_compiles(one_chip):
    s = lambda *a: _spec(one_chip, *a)
    _compile(
        lambda *z: pq_score.pq_score_batch(*z, interpret=False),
        s((N + 1, PQ_M), jnp.uint8), s((N + 1, A)), s((B, CAP), jnp.int32),
        s((B, CAP), jnp.bool_), s((B, PQ_M, PQ_KS)), s((B, T, A)), s((B, T, A)),
    )


@pytest.mark.parametrize("kernel", ["visit_step", "filter_distance_batch"])
def test_unaligned_width_compiles(one_chip, kernel):
    """A width that is not a multiple of 128 (48 here, like 96, 100 or
    GIST's 960) cannot be DMA'd one row at a time; those rows take the
    XLA-gather route and still lower to a Mosaic kernel."""
    from repro.kernels.row_gather import dma_rows

    d = 48
    assert not dma_rows("l2", d)
    s = lambda *a: _spec(one_chip, *a)
    if kernel == "visit_step":
        _compile(
            lambda vec, att, i, m, q, lo, hi: visit_step.visit_step(
                vec, att, None, i, m, q, lo, hi, interpret=False),
            s((N + 1, d)), s((N + 1, A)), s((V,), jnp.int32), s((V,), jnp.bool_),
            s((d,)), s((T, A)), s((T, A)),
        )
    else:
        _compile(
            lambda *z: filter_distance.filter_distance_batch(*z, interpret=False),
            s((N + 1, d)), s((N + 1, A)), s((B, CAP), jnp.int32), s((B, CAP), jnp.bool_),
            s((B, d)), s((B, T, A)), s((B, T, A)),
        )


@pytest.mark.parametrize("kernel", ["visit_step", "filter_distance_batch"])
def test_wide_rows_compile(one_chip, kernel):
    """GIST's 960-d rows at 2^20 rows, as the served program holds them: the
    XLA-gather route's (rb, 960) row blocks fit the kernel's fast memory at
    the block size a traced call takes, with tombstones as the mutable tier
    passes them."""
    from repro.kernels.row_gather import dma_rows

    d, n = 960, 1 << 20
    assert not dma_rows("l2", d)
    s = lambda *a: _spec(one_chip, *a)
    if kernel == "visit_step":
        def fn(vectors, attrs, live, idx, mask, q, lo, hi):
            return jax.vmap(lambda i, m, q1: visit_step.visit_step(
                vectors, attrs, live, i, m, q1, lo, hi, interpret=False))(idx, mask, q)

        _compile(fn, s((n + 1, d)), s((n + 1, A)), s((n + 1,), jnp.bool_),
                 s((B, V), jnp.int32), s((B, V), jnp.bool_), s((B, d)), s((T, A)), s((T, A)))
    else:
        _compile(
            lambda *z: filter_distance.filter_distance_batch(*z, interpret=False),
            s((n + 1, d)), s((n + 1, A)), s((B, CAP), jnp.int32), s((B, CAP), jnp.bool_),
            s((B, d)), s((B, T, A)), s((B, T, A)),
        )


@pytest.fixture(scope="module")
def served(one_chip):
    """The served exact program (mutable fan-out, planner on, fused visit
    kernel) at 2^20 rows, compiled for one described chip for each term
    bucket the benchmark serves: ``{T: compiled}``."""
    from repro.compass import CompassParams
    from repro.core import predicate as P
    from repro.core.index import BuildConfig, build_index
    from repro.core.mutable import MutableIndex, mutable_search
    from repro.data.synthetic import make_vector_corpus

    with pytest.MonkeyPatch.context() as mp:
        # the engine picks interpret mode from the host platform: steer it
        for mod in (visit_step, filter_distance, pq_score, ivf_score):
            mp.setattr(mod, "default_interpret", lambda: False)
        n_small = 1500
        x, attrs, _ = make_vector_corpus(n_small, D, A, n_modes=16, seed=0)
        snap = MutableIndex(build_index(x, attrs, BuildConfig(nlist=NLIST // 64)),
                            delta_cap=64).snapshot()
        n_rows, n_real = snap.index.n_records, 1 << 20

        def real(leaf):
            grow = {n_rows: n_real, n_rows + 1: n_real + 1}
            return _spec(one_chip, tuple(grow.get(d, d) for d in leaf.shape), leaf.dtype)

        pm = CompassParams(k=10, ef=64, planner=True, backend="pallas")
        out = {}
        for terms in (1, T):
            pred = P.Predicate(_spec(one_chip, (B, terms, A)), _spec(one_chip, (B, terms, A)))
            out[terms] = _compile(
                lambda i, g, d, q, p: mutable_search(i, g, d, q, p, pm),
                jax.tree.map(real, snap.index), jax.tree.map(real, snap.base_gids),
                jax.tree.map(real, snap.delta), _spec(one_chip, (B, D)), pred,
            )
        return out


def test_served_exact_search_compiles(served):
    """The served exact program at 2^20 rows fits one chip.  Guards against
    the vmapped engine broadcasting the corpus per lane into a kernel
    operand (B copies of the table would not fit in HBM)."""
    mem = served[1].memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 8 << 30


@pytest.mark.parametrize("terms", [1, T])
def test_served_program_stages_resolve(served, terms):
    """Every device operation of the served program can be put down to an
    engine stage by the benchmark's rule (``bench/scopes.py``)."""
    from bench import scopes

    prog = scopes.Program(served[terms].as_text())
    assert prog.main_loop is not None
    stage = {name: prog.stage(name) for name in prog.instrs}
    # B.NEXT runs in the loop, and reads single elements of the attribute
    # runs: no per-lane copy of a 2^20-wide run (one per lane and term) nor
    # the runs broadcast to every lane
    assert any(stage[n] == "compass/engine/bnext" for n in prog.instrs if prog.in_main_loop(n))
    wide = [i for i in prog.instrs.values()
            if i.shape.startswith((f"f32[{B * terms},1,{1 << 20}]",
                                   f"f32[{B * terms},1,1,{1 << 20}]", f"f32[{B},{A},{1 << 20}]"))
            and prog.in_main_loop(i.name)]
    assert not wide, [i.name for i in wide]
    # the Pallas kernels inside the loop are the fused visit step's calls
    kernels = re.findall(r'^\s+(?:ROOT\s+)?%?([\w.\-]+) = .*custom_call_target="tpu_custom_call"',
                         served[terms].as_text(), re.M)
    in_loop = [n for n in kernels if prog.in_main_loop(n)]
    assert in_loop and {stage[n] for n in in_loop} == {"compass/visit_step"}
    assert {stage[n] for n in kernels} <= {"compass/visit_step", "compass/ivf_score",
                                           "compass/filter_distance"}
    # every sort is a queue sort, or one of these: the centroid ranking at
    # OPEN, G.NEXT's two-hop top_k, the delta's top_k, and those XLA makes
    # for a scatter (inside the loop, held by it; outside it, in no scope)
    named = {"compass/engine/sort", "compass/engine/open", "compass/engine/gnext",
             "compass/mutable/delta", "compass/engine/loop"}
    for name, ins in prog.instrs.items():
        if ins.opcode == "sort":
            assert stage[name] in named or (
                stage[name] == scopes.UNSCOPED and ins.op_name is None
                and not prog.in_main_loop(name)), (name, stage[name])
    assert sum(stage[n] == "compass/engine/sort" for n, i in prog.instrs.items()
               if i.opcode == "sort") >= 10
    # nothing that runs inside the engine loop, however deeply nested, is
    # left unscoped
    assert not [n for n in prog.instrs if prog.in_main_loop(n) and stage[n] == scopes.UNSCOPED]


@pytest.mark.parametrize("terms", [1, T])
def test_served_loop_keeps_no_per_lane_row_table(served, terms):
    """The engine loop carries the visited set as packed words, ``u32[B,
    W]``, and nothing in it holds a bool row of the corpus per lane: no
    ``(B, >= 2^20)`` bool array in any layout, flat or not, and no
    ``dynamic-update-slice`` into a 2^20-wide operand, the lane-by-lane
    relayout XLA made of a ``(B, N+1)`` bool table on every visit."""
    from bench import scopes

    prog = scopes.Program(served[terms].as_text())
    wide, words = 1 << 20, 33_792  # words == state.visited_words(2^20)
    in_loop = [i for n, i in prog.instrs.items() if prog.in_main_loop(n)]

    def arrays(ins):
        return [(dt, [int(d) for d in dims.split(",") if d])
                for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]", ins.shape)]

    def per_lane_row(dt, dims):
        return dt == "pred" and ((B in dims and max(dims) >= wide)
                                 or (len(dims) == 1 and dims[0] >= B * wide))

    per_lane_bool = [i.name for i in in_loop if any(per_lane_row(*a) for a in arrays(i))]
    assert not per_lane_bool, (len(per_lane_bool), per_lane_bool[:10])
    wide_updates = [i.name for i in in_loop if i.opcode == "dynamic-update-slice"
                    and any(max(dims, default=0) >= wide for _, dims in arrays(i))]
    assert not wide_updates, wide_updates
    assert any(("u32", [B, words]) in arrays(i) for i in in_loop)
