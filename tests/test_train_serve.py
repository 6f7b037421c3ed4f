"""Integration: short training runs (loss decreases, checkpoint restart
continues identically), continuous-batching serving, filtered RAG."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.launch.train import train_loop
from repro.models.model import init_params


@pytest.fixture(scope="module")
def tiny_cfg():
    return dataclasses.replace(
        reduced(get_config("tinyllama-1.1b")),
        n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
    )


def test_training_loss_decreases(tiny_cfg):
    _, losses = train_loop(tiny_cfg, steps=30, global_batch=4, seq_len=64, log=lambda *_: None)
    assert losses[-1] < losses[0]


def test_checkpoint_restart_resumes(tmp_path, tiny_cfg):
    d = str(tmp_path / "run")
    _, full = train_loop(
        tiny_cfg, steps=20, global_batch=4, seq_len=64, ckpt_dir=d, ckpt_every=10,
        log=lambda *_: None,
    )
    # restart from step-10 checkpoint and replay 10..20
    import shutil

    shutil.rmtree(d + "/step_00000020")
    _, resumed = train_loop(
        tiny_cfg, steps=20, global_batch=4, seq_len=64, ckpt_dir=d, ckpt_every=100,
        log=lambda *_: None,
    )
    # deterministic data + restored state => same trailing losses
    np.testing.assert_allclose(resumed[-3:], full[-3:], rtol=1e-3, atol=1e-3)


def test_microbatched_equals_single_batch_grads(tiny_cfg):
    """Gradient accumulation invariant: mean of 4 microbatch grads equals
    the full-batch grad (compared pre-optimizer: Adam's rsqrt amplifies
    numerically-tiny grad differences into sign flips)."""
    from repro.data.synthetic import DataConfig, SyntheticTokens
    from repro.train.step import TrainConfig, make_loss_fn

    data = SyntheticTokens(DataConfig(tiny_cfg.vocab_size, 32, 8, seed=1))
    batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}
    params = init_params(tiny_cfg, jax.random.PRNGKey(0))
    tc = TrainConfig(n_microbatches=1, remat=False)
    loss_fn = make_loss_fn(tiny_cfg, tc)
    l_full, g_full = jax.value_and_grad(loss_fn)(params, batch)

    nm = 4
    micro = jax.tree.map(lambda x: x.reshape((nm, x.shape[0] // nm) + x.shape[1:]), batch)
    l_acc, g_acc = 0.0, jax.tree.map(jnp.zeros_like, g_full)
    for i in range(nm):
        mb = jax.tree.map(lambda x: x[i], micro)
        l, g = jax.value_and_grad(loss_fn)(params, mb)
        l_acc += float(l) / nm
        g_acc = jax.tree.map(lambda a, b: a + b / nm, g_acc, g)
    assert l_acc == pytest.approx(float(l_full), rel=1e-4)
    for a, b in zip(jax.tree.leaves(g_acc), jax.tree.leaves(g_full)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-2, atol=5e-4)


def test_continuous_batcher_serves_requests(tiny_cfg):
    from repro.serving.scheduler import ContinuousBatcher, Request

    params = init_params(tiny_cfg, jax.random.PRNGKey(0))
    b = ContinuousBatcher(tiny_cfg, params, n_slots=2, max_seq=64)
    rng = np.random.default_rng(0)
    reqs = [
        Request(rid=i, prompt=rng.integers(0, 256, 5).astype(np.int32), max_tokens=4)
        for i in range(5)
    ]
    for r in reqs:
        b.submit(r)
    b.run_until_done()
    for r in reqs:
        assert r.done and len(r.out_tokens) == 4


def test_prefill_bucket_shapes():
    from repro.serving.scheduler import prefill_bucket

    assert [prefill_bucket(p, 64) for p in (1, 5, 8, 9, 33)] == [8, 8, 8, 16, 64]
    assert prefill_bucket(60, 64) == 64  # capped at max_seq
    # recurrent configs (SSM/hybrid) must prefill exact-length: pad tokens
    # would be scanned into the recurrent state
    assert prefill_bucket(5, 64, recurrent=True) == 5
    with pytest.raises(ValueError):
        prefill_bucket(65, 64)


def test_batcher_ragged_prompt_lengths_match_padded_prefill(tiny_cfg):
    """Prompts straddling prefill buckets (3, 8, 13 tokens) decode the same
    tokens as a prompt-length-identical run — bucketed prefill is
    output-neutral for attention configs."""
    from repro.serving.scheduler import ContinuousBatcher, Request

    params = init_params(tiny_cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (3, 8, 13)]

    def serve(n_slots):
        b = ContinuousBatcher(tiny_cfg, params, n_slots=n_slots, max_seq=64)
        reqs = [Request(rid=i, prompt=p, max_tokens=4) for i, p in enumerate(prompts)]
        for r in reqs:
            b.submit(r)
        b.run_until_done()
        return [r.out_tokens for r in reqs]

    # single-slot (sequential, each prompt prefilled alone) == 3-slot batch
    assert serve(1) == serve(3)


def test_filtered_rag_respects_predicate(tiny_cfg):
    from repro.core import predicate as P
    from repro.core.index import BuildConfig
    from repro.serving.rag import RagIndex

    rng = np.random.default_rng(1)
    params = init_params(tiny_cfg, jax.random.PRNGKey(0))
    doc_tokens = rng.integers(0, 256, (48, 8)).astype(np.int32)
    doc_attrs = rng.uniform(size=(48, 2)).astype(np.float32)
    rag = RagIndex.build(params, tiny_cfg, doc_tokens, doc_attrs,
                         BuildConfig(m=8, nlist=4))
    pred = P.Pred.le(0, 0.4).tensor(2)
    prompts = np.stack([rng.integers(0, 256, 8).astype(np.int32) for _ in range(4)])
    ids = rag.retrieve(params, tiny_cfg, prompts, pred, k=3, ef=16)
    found_any = False
    for b_ in range(4):
        for i in ids[b_]:
            if i < 48:
                found_any = True
                assert doc_attrs[i, 0] <= 0.4 + 1e-6
    assert found_any
    # the serving-layer path returns the same docs (padding is
    # result-neutral; same CompassParams via make_service)
    service = rag.make_service(k=3, ef=16, batch_size=4, max_wait_s=0.0)
    ids_svc = rag.retrieve(params, tiny_cfg, prompts, pred, k=3, service=service)
    np.testing.assert_array_equal(ids_svc, ids)
    assert service.stats()["compiles"] == 1
