"""Distributed Compass search: executed on 8 virtual devices in a
subprocess (device count must be set before jax initializes), validating
that corpus-sharded search + global top-k merge matches brute force."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import predicate as P
    from repro.core.baselines import brute_force, recall
    from repro.core.distributed import build_sharded_index, make_distributed_search
    from repro.core.index import BuildConfig
    from repro.compass import CompassParams
    from repro.data.synthetic import make_vector_corpus

    n, d, a, n_shards = 8000, 24, 4, 8
    x, attrs, queries = make_vector_corpus(n, d, a, n_modes=32, seed=3)
    queries = queries[:8]
    sidx = build_sharded_index(x, attrs, n_shards, BuildConfig(m=12, nlist=16))
    mesh = jax.make_mesh((8,), ("shard",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    pm = CompassParams(k=10, ef=64)
    search = make_distributed_search(mesh, pm)
    rng = np.random.default_rng(0)
    preds = []
    for _ in range(8):
        lo = rng.uniform(0, 0.7)
        preds.append(P.Pred.and_(P.Pred.range(0, lo, lo + 0.3),
                                 P.Pred.range(1, 0.2, 0.8)).tensor(a))
    pred = P.stack_predicates(preds)
    with jax.set_mesh(mesh):
        ids, dists = search(sidx, jnp.asarray(queries), pred)
    # map global ids back: shard * n_local + local, n_local = n // n_shards
    truth = brute_force(jnp.asarray(x), jnp.asarray(attrs), jnp.asarray(queries), pred, 10)
    n_loc = n // n_shards
    gids = np.asarray(ids)
    # translate shard-local ids to corpus ids (shards were contiguous splits)
    corpus_ids = np.where(gids < n, (gids // n_loc) * n_loc + gids % n_loc, n)
    r = recall(corpus_ids, np.asarray(truth.ids), np.asarray(truth.dists), n)
    print("RECALL", r)
    assert r >= 0.9, r
    # distances sorted ascending and finite where valid
    dd = np.asarray(dists)
    for b in range(dd.shape[0]):
        fin = dd[b][np.isfinite(dd[b])]
        assert np.all(np.diff(fin) >= 0)
    print("DISTRIBUTED_OK")
    """
)


@pytest.mark.slow
def test_distributed_search_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT],
        capture_output=True, text=True, timeout=1200, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "DISTRIBUTED_OK" in out.stdout, out.stdout + out.stderr


@pytest.mark.slow
def test_dryrun_single_cell_subprocess(tmp_path):
    """The dry-run driver itself (512 virtual devices) on the smallest cell;
    its record goes to ``tmp_path`` so the committed records stay as they are."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    out = subprocess.run(
        [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", "granite-moe-1b-a400m", "--shape", "decode_32k",
            "--out-dir", str(tmp_path),
        ],
        capture_output=True, text=True, timeout=1200, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert "OK granite-moe-1b-a400m x decode_32k" in out.stdout, out.stdout[-2000:] + out.stderr[-2000:]
    assert (tmp_path / "granite-moe-1b-a400m_decode_32k_16x16.json").exists()
