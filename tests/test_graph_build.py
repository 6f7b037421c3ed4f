"""Graph build blocks: NN-descent and the prune run over the corpus in
blocks whose size follows the width, and the block size never changes the
graph."""
import numpy as np
import pytest

from repro.core import graph_build
from repro.data.synthetic import make_vector_corpus


@pytest.mark.parametrize("d,rows", [(48, 1024), (128, 1024), (256, 512), (960, 128)])
def test_block_rows_follow_the_width(d, rows):
    """Up to 128-d a block is 1024 rows; wider, no more bytes than that."""
    assert graph_build._block_rows(d) == rows


def test_wide_block_gives_the_graph_of_1024_row_blocks(monkeypatch):
    x, _, _ = make_vector_corpus(300, 960, 1, n_modes=4, seed=3)
    kw = dict(m=4, n_candidates=8, metric="l2", seed=0)
    wide = graph_build.build_graph(x, **kw)
    monkeypatch.setattr(graph_build, "_block_rows", lambda d: 1024)
    whole = graph_build.build_graph(x, **kw)
    np.testing.assert_array_equal(np.asarray(wide.neighbors), np.asarray(whole.neighbors))
    assert int(wide.entry) == int(whole.entry)


@pytest.mark.parametrize("d,rows", [(48, 4096), (128, 4096), (256, 2048), (960, 512)])
def test_repair_sample_follows_the_width(d, rows):
    """A repair round samples 4096 reached rows up to 128-d; wider, no more
    values than that."""
    assert graph_build._rows_within(graph_build._REPAIR_SAMPLE, d) == rows


def _blas_threads():
    set_threads = graph_build._blas_set_threads()
    if set_threads is None:
        return None
    count = set_threads(1)
    set_threads(count)
    return count


@pytest.mark.parametrize("serial", ["one worker", "no BLAS setting"])
@pytest.mark.parametrize("d", [32, 960])
def test_pools_on_threads_give_the_graph_of_one_thread(monkeypatch, d, serial):
    """The pools on a thread pool over one BLAS thread each give the graph
    of one cluster at a time, and the BLAS threads are put back."""
    x, _, _ = make_vector_corpus(600, d, 1, n_modes=6, seed=5)
    kw = dict(m=6, n_candidates=12, n_build_clusters=24, metric="l2", seed=0)
    before = _blas_threads()
    threaded = graph_build.build_graph(x, **kw)
    assert _blas_threads() == before
    if serial == "one worker":
        monkeypatch.setattr(graph_build, "_HOST_WORKERS", 1)
    else:
        monkeypatch.setattr(graph_build, "_blas_set_threads", lambda: None)
    one = graph_build.build_graph(x, **kw)
    np.testing.assert_array_equal(np.asarray(threaded.neighbors), np.asarray(one.neighbors))
    assert int(threaded.entry) == int(one.entry)


def test_ivf_kmeans_compiled_ahead_is_the_plain_call():
    """build_index compiles the IVF k-means beside the graph build; its
    centroids and assignments are the plain call's, bit for bit."""
    import jax.numpy as jnp

    from repro.core.index import BuildConfig, build_index
    from repro.core.kmeans import kmeans

    x, attrs, _ = make_vector_corpus(800, 24, 2, n_modes=4, seed=9)
    cfg = BuildConfig(m=8, nlist=16, kmeans_iters=3, seed=4)
    index = build_index(x, attrs, cfg)
    km = kmeans(jnp.asarray(x), cfg.nlist, iters=cfg.kmeans_iters, seed=cfg.seed, metric="l2")
    np.testing.assert_array_equal(np.asarray(index.centroids), np.asarray(km.centroids))
    np.testing.assert_array_equal(np.asarray(index.cattrs.assignments), np.asarray(km.assignments))
