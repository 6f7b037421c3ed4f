"""The engine's visited set: a packed uint32 bitmap per lane, read by
``state.is_visited`` and written by ``state.mark_visited``, checked bit for
bit against a plain bool vector under ``vmap`` over lanes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import state as S

SIZES = [1, 31, 32, 33, 1023, 1024, 4097]
LANES, ROUNDS = 4, 4


def _pack(ref: np.ndarray, words: int) -> np.ndarray:
    """(L, n + 1) bool -> (L, words) uint32, bit ``i & 31`` of word ``i >> 5``."""
    bits = np.zeros((ref.shape[0], words * 32), np.uint64)
    bits[:, : ref.shape[1]] = ref
    return (bits.reshape(ref.shape[0], words, 32) << np.arange(32, dtype=np.uint64)).sum(
        -1).astype(np.uint32)


def _boundary_ids(n: int) -> np.ndarray:
    """Ids that share words and sit on word edges, the sentinel included."""
    ids = {0, 1, 30, 31, 32, 33, 63, 64, 65, 1023, 1024, n - 1, n}
    return np.array(sorted(i for i in ids if 0 <= i <= n), np.int32)


mark = jax.jit(jax.vmap(S.mark_visited))
bits_of = jax.jit(jax.vmap(S.is_visited))


def test_visited_words():
    assert S.visited_words(1 << 20) == 33_792
    for n in SIZES + [1 << 20]:
        w = S.visited_words(n)
        assert w % 1024 == 0 and 32 * w >= n + 1 > 32 * (w - 1024)


@pytest.mark.parametrize("n", SIZES)
def test_bitmap_matches_bool_reference(n):
    rng = np.random.default_rng(n)
    words = S.visited_words(n)
    all_ids = jnp.broadcast_to(jnp.arange(n + 1, dtype=jnp.int32), (LANES, n + 1))
    ref = np.zeros((LANES, n + 1), bool)
    visited = jnp.zeros((LANES, words), jnp.uint32)
    v = min(n + 1, 24)
    for r in range(ROUNDS):
        if r == 0:  # ids that share a word, at word boundaries, all set at once
            ids = np.broadcast_to(_boundary_ids(n), (LANES, len(_boundary_ids(n)))).copy()
            mask = np.ones(ids.shape, bool)
            mask[1] = rng.random(ids.shape[1]) < 0.5
        else:  # random distinct ids per lane, the sentinel among them
            ids = np.stack([rng.permutation(n + 1)[:v] for _ in range(LANES)]).astype(np.int32)
            perm0 = rng.permutation(n)  # lane 0 always offers the sentinel
            ids[0] = np.concatenate([[n], perm0[: v - 1]])
            mask = rng.random(ids.shape) < 0.6
        # the writer's precondition: masked ids distinct and not yet visited
        mask &= ~np.take_along_axis(ref, ids, axis=1)
        np.testing.assert_array_equal(
            np.asarray(bits_of(visited, jnp.asarray(ids))),
            np.take_along_axis(ref, ids, axis=1))
        visited = mark(visited, jnp.asarray(ids), jnp.asarray(mask))
        for lane in range(LANES):
            ref[lane, ids[lane][mask[lane]]] = True
        np.testing.assert_array_equal(np.asarray(visited), _pack(ref, words))
        np.testing.assert_array_equal(np.asarray(bits_of(visited, all_ids)), ref)
    assert ref.any(axis=1).all()


@pytest.mark.parametrize("n", SIZES)
def test_masked_out_ids_set_nothing(n):
    """A masked-out entry adds 0, so the sentinel's word, which every
    masked-out visit slot points at, never gains a bit from them."""
    words = S.visited_words(n)
    ids = jnp.full((LANES, 64), n, jnp.int32).at[:, :8].set(jnp.arange(8) % (n + 1))
    visited = mark(jnp.zeros((LANES, words), jnp.uint32), ids, jnp.zeros(ids.shape, bool))
    assert not np.asarray(visited).any()
    # a set sentinel stays one bit however many masked-out slots point at it
    ids1 = jnp.full((LANES, 1), n, jnp.int32)
    visited = mark(visited, ids1, jnp.ones((LANES, 1), bool))
    visited = mark(visited, ids, jnp.zeros(ids.shape, bool))
    want = np.zeros((LANES, n + 1), bool)
    want[:, n] = True
    np.testing.assert_array_equal(np.asarray(visited), _pack(want, words))
