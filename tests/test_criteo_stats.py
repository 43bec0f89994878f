"""CriteoStats: the deterministic Criteo-marginal-matched generator.

The real-data-AUC proxy (round-4 review, ask #3): marginals pinned to public
Kaggle Criteo summary statistics, label from a hash-derived logistic
model with a computable Bayes ceiling. These tests pin the statistical
contract the AUC protocol (modelzoo/benchmark/auc_protocol.py) relies on.
"""
import numpy as np
import pytest

from deeprec_tpu.data.synthetic import (
    CRITEO_DENSE_MISSING,
    CRITEO_KAGGLE_CARDINALITIES,
    CRITEO_KAGGLE_CTR,
    CriteoStats,
    _auc,
)


@pytest.fixture(scope="module")
def gen():
    return CriteoStats(batch_size=1024, seed=0)


def test_batch_at_is_pure(gen):
    a = gen.batch_at(7)
    b = gen.batch_at(7)
    assert sorted(a) == sorted(b)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k])
    # a fresh instance reproduces the same stream
    c = CriteoStats(batch_size=1024, seed=0).batch_at(7)
    for k in a:
        np.testing.assert_array_equal(a[k], c[k])


def test_streams_differ_by_index_seed_split(gen):
    a = gen.batch_at(0)
    for other in (
        gen.batch_at(1),
        CriteoStats(batch_size=1024, seed=1).batch_at(0),
        CriteoStats(batch_size=1024, seed=0, split="eval").batch_at(0),
    ):
        assert not np.array_equal(a["C3"], other["C3"])


def test_ctr_matches_kaggle(gen):
    out, _ = gen.probs_at(0, 200_000)
    assert abs(out["label"].mean() - CRITEO_KAGGLE_CTR) < 0.01


def test_cardinalities_respected(gen):
    out = gen.batch_at(0)
    for c, card in enumerate(gen.cards):
        ids = out[f"C{c + 1}"]
        assert ids.min() >= 0 and ids.max() < card
        assert card == min(CRITEO_KAGGLE_CARDINALITIES[c], 1 << 22)


def test_zipf_head_mass(gen):
    """Heavy tails: the top-100 ids of a multi-million-cardinality column
    carry most of the mass (real Criteo columns are this skewed)."""
    out, _ = gen.probs_at(0, 100_000)
    ids = out["C3"]  # cardinality 10.1M (capped 4.2M)
    cnt = np.bincount(ids)
    share = np.sort(cnt)[::-1][:100].sum() / cnt.sum()
    assert share > 0.5, share


def test_dense_missingness_and_shape(gen):
    out, _ = gen.probs_at(0, 50_000)
    for i in range(13):
        col = out[f"I{i + 1}"]
        assert col.shape == (50_000, 1)
        zero_rate = float((col == 0).mean())
        assert abs(zero_rate - CRITEO_DENSE_MISSING[i]) < 0.02, (i, zero_rate)


def test_bayes_ceiling_band():
    """The task's Bayes AUC sits in the real-Criteo regime (~0.79) and is
    stable across seeds (the hidden task is seed-independent)."""
    a = CriteoStats(seed=0).bayes_auc(100_000)
    b = CriteoStats(seed=3).bayes_auc(100_000)
    assert 0.77 < a < 0.82, a
    assert abs(a - b) < 0.01


def test_label_is_learnable_fast():
    """A linear model on the strongest column's one-hot must beat
    coin-flip from a modest sample — the signal is real, not noise."""
    g = CriteoStats(batch_size=4096, seed=0)
    # strongest column = argmax strength
    c = int(np.argmax(g.strength))
    card = g.cards[c]
    if card > 1 << 16:
        pytest.skip("strongest column too wide for the quick probe")
    w = np.zeros(card)
    n = np.zeros(card)
    for i in range(12):
        out = g.batch_at(i)
        ids, y = out[f"C{c + 1}"], out["label"]
        np.add.at(w, ids, y)
        np.add.at(n, ids, 1)
    rate = (w + 1.0) / (n + 4.0)  # smoothed per-id CTR
    ev = g.batch_at(100)
    auc = _auc(ev["label"], rate[ev[f"C{c + 1}"]])
    assert auc > 0.55, auc


def test_save_restore_stream_position():
    g = CriteoStats(batch_size=256, seed=0)
    g.batch(), g.batch()
    st = g.save()
    a = g.batch()
    g2 = CriteoStats(batch_size=256, seed=0)
    g2.restore(st)
    b = g2.batch()
    np.testing.assert_array_equal(a["C1"], b["C1"])


def test_auc_helper_exact():
    lab = np.asarray([1, 0, 1, 0, 0], np.float32)
    score = np.asarray([0.9, 0.1, 0.8, 0.7, 0.2], np.float32)
    # pairs: (1>.1),(.9>.7),(.9>.2),(.8>.1),(.8>.7),(.8>.2) all correct -> 1.0
    assert _auc(lab, score) == 1.0
    assert _auc(lab, 1 - score) == 0.0
    assert _auc(np.ones(3, np.float32), score[:3]) == 0.5
    # ties take the midrank: order of tied entries must not matter
    assert _auc(np.asarray([1.0, 0.0]), np.asarray([0.5, 0.5])) == 0.5
    assert _auc(np.asarray([0.0, 1.0]), np.asarray([0.5, 0.5])) == 0.5
    assert _auc(
        np.asarray([1, 0, 1, 0], np.float32),
        np.asarray([0.7, 0.7, 0.2, 0.2], np.float32),
    ) == 0.5


def test_consumed_index_checkpoints_behind_prefetch_ring():
    """ADVICE round-5 #2: under a depth-2 prefetch ring the producer index
    runs ahead of what the train loop consumed; save() must checkpoint the
    CONSUMED position so kill-and-resume replays every unconsumed batch
    exactly once."""
    import time

    from deeprec_tpu.data.prefetch import Prefetcher

    g = CriteoStats(batch_size=64, seed=0)
    g.attach_consumer()  # wiring-time: BEFORE the ring's producer runs ahead
    pf = Prefetcher(iter(g), depth=2, transform=lambda b: b,
                    on_consume=g.mark_consumed)
    try:
        # a save BEFORE the first delivery must report position 0 even
        # though the ring's producer is already ahead
        deadline0 = time.time() + 5.0
        while g._index == 0 and time.time() < deadline0:
            time.sleep(0.01)
        assert g._index > 0 and g.save()["index"] == 0
        consumed = [next(pf) for _ in range(3)]
        # let the producer run the ring ahead of the consumer
        deadline = time.time() + 5.0
        while g._index <= 3 and time.time() < deadline:
            time.sleep(0.01)
        assert g._index > 3, "producer never ran ahead (ring broken?)"
        st = g.save()
        assert st["index"] == 3  # consumed, NOT the producer position
    finally:
        pf.close()

    # the consumer saw exactly batches 0..2, in order
    for i, b in enumerate(consumed):
        np.testing.assert_array_equal(b["C1"], g.batch_at(i)["C1"])

    # kill-and-resume: the restored stream hands out batch 3 next — the
    # first batch the dead run never trained on — exactly once
    g2 = CriteoStats(batch_size=64, seed=0)
    g2.restore(st)
    pf2 = Prefetcher(iter(g2), depth=2, transform=lambda b: b,
                     on_consume=g2.mark_consumed)
    try:
        nxt = next(pf2)
        np.testing.assert_array_equal(nxt["C1"], g.batch_at(3)["C1"])
        assert g2.save()["index"] == 4
    finally:
        pf2.close()

    # unstaged use keeps the legacy producer-position semantics
    g3 = CriteoStats(batch_size=64, seed=0)
    g3.batch(), g3.batch()
    assert g3.save()["index"] == 2
