"""The bucketed scoring engine: envelope rounding, padded-score parity
with direct unpadded scoring, the steady-state ZERO-recompile guarantee
under a randomized request replay, the stats ledger, and the spans of a
dispatch."""
import jax
import numpy as np
import jax.numpy as jnp
import pytest

from repro import obs
from repro.serve import (
    BundleRequest,
    ScoreBundle,
    ScoringEngine,
    compress,
    quantize,
    score_bundles,
    synthetic_requests,
)
from repro.serve.engine import _round_up
from repro.serve.score import as_model

D, M = 700, 3


@pytest.fixture(scope="module")
def theta():
    rng = np.random.default_rng(0)
    th = rng.normal(size=(D, 2 * M)).astype(np.float32) * 0.3
    th[rng.random(D) >= 0.2] = 0.0
    return jnp.asarray(th)


def _direct_scores(theta, req: BundleRequest) -> np.ndarray:
    """Unpadded single-bundle scoring through the plain score layer."""
    n = req.ad_ids.shape[0]
    bundle = ScoreBundle(
        user_ids=jnp.asarray(req.user_ids[None], jnp.int32),
        user_vals=jnp.asarray(req.user_vals[None]),
        ad_ids=jnp.asarray(req.ad_ids, jnp.int32),
        ad_vals=jnp.asarray(req.ad_vals),
        session_id=jnp.zeros((n,), jnp.int32))
    return np.asarray(score_bundles(theta, bundle))


# ------------------------------------------------------------ envelopes
def test_round_up_bucket_edges():
    assert _round_up(1, (8, 16)) == 8
    assert _round_up(8, (8, 16)) == 8
    assert _round_up(9, (8, 16)) == 16
    assert _round_up(17, (8, 16)) == 32  # past the top: multiples of it
    assert _round_up(33, (8, 16)) == 48
    with pytest.raises(ValueError):
        _round_up(0, (8, 16))


def test_envelope_uses_configured_buckets(theta):
    eng = ScoringEngine(theta, k_buckets=(4, 8), n_buckets=(2, 4))
    req = synthetic_requests(1, num_features=D, k_user=(5, 5), k_ad=(3, 3),
                             n_ads=(3, 3))[0]
    assert eng.envelope(req) == (8, 4, 4)


# ------------------------------------------------------ score parity
def test_engine_scores_match_direct(theta):
    """Padding to the envelope must not change the scores beyond fp
    reassociation of the padded-K contraction (<= 1e-6)."""
    eng = ScoringEngine(theta)
    for req in synthetic_requests(12, num_features=D, seed=1):
        np.testing.assert_allclose(eng.score(req), _direct_scores(theta, req),
                                   rtol=1e-6, atol=1e-6)


def test_engine_pruned_equals_full(theta):
    """The engine on a pruned artifact returns BIT-identical scores to
    the engine on the full Theta (same envelopes, same kernel path)."""
    full = ScoringEngine(theta)
    pruned = ScoringEngine(compress(theta))
    for req in synthetic_requests(8, num_features=D, seed=2):
        np.testing.assert_array_equal(full.score(req), pruned.score(req))


# --------------------------------------------------- steady-state cache
def test_zero_recompiles_on_randomized_replay(theta):
    rng = np.random.default_rng(3)
    eng = ScoringEngine(theta)
    requests = synthetic_requests(40, num_features=D, seed=4)
    eng.warm({eng.envelope(r) for r in requests})
    warm_compiles = eng.stats.compiles
    assert warm_compiles == len({eng.envelope(r) for r in requests})
    first = {}
    for _ in range(3):  # three shuffled replays of the same traffic
        order = rng.permutation(len(requests))
        for i in order:
            p = eng.score(requests[i])
            if i in first:
                np.testing.assert_array_equal(p, first[i])  # deterministic
            else:
                first[i] = p
    assert eng.stats.compiles == warm_compiles, "steady state recompiled"
    assert eng.stats.requests == 3 * len(requests)


def test_new_envelope_compiles_exactly_once(theta):
    eng = ScoringEngine(theta, k_buckets=(8,), n_buckets=(4,))
    reqs = synthetic_requests(4, num_features=D, k_user=(6, 6), k_ad=(4, 4),
                              n_ads=(3, 3), seed=5)
    eng.score(reqs[0])
    assert eng.stats.compiles == 1
    eng.score_many(reqs[1:])
    assert eng.stats.compiles == 1  # same envelope, cached executable
    big = synthetic_requests(1, num_features=D, k_user=(10, 10), k_ad=(4, 4),
                             n_ads=(3, 3), seed=6)[0]
    eng.score(big)  # Ku 10 -> bucket 16 (8x2): a genuinely new envelope
    assert eng.stats.compiles == 2


def test_stats_ledger(theta):
    eng = ScoringEngine(theta)
    requests = synthetic_requests(10, num_features=D, seed=7)
    eng.score_many(requests)
    s = eng.stats
    assert s.requests == 10
    assert s.candidates == sum(r.ad_ids.shape[0] for r in requests)
    assert sum(s.bucket_hits.values()) == 10
    assert s.dispatches == 10 and s.slots == 10  # all G=1 dispatches
    assert s.occupancy == 1.0
    assert s.score_seconds > 0 and s.compile_seconds > 0
    assert s.latency_us > 0 and s.candidates_per_sec > 0
    d = s.as_dict()
    assert d["requests"] == 10 and len(d["bucket_hits"]) == len(s.bucket_hits)
    assert d["occupancy"] == 1.0 and d["dispatches"] == 10


def test_dispatch_span_has_four_children_in_order(theta):
    """One score_batch dispatch records ``serve/dispatch`` with exactly
    pad, launch, sync and readback nested inside it, in that order, on
    the calling thread."""
    reqs = synthetic_requests(3, num_features=D, k_user=(5, 5), k_ad=(4, 4),
                              n_ads=(3, 3), seed=12)
    eng = ScoringEngine(theta)
    eng.warm({eng.envelope(reqs[0])}, batch_sizes=eng.g_buckets)
    tracer = obs.Tracer(enabled=True)
    prev = obs.set_tracer(tracer)
    try:
        got = eng.score_batch(reqs)
    finally:
        obs.set_tracer(prev)
    spans = sorted((e for e in tracer.events() if e["ph"] == "X"),
                   key=lambda e: e["ts"])
    assert [e["name"] for e in spans] == [
        "serve/dispatch", "serve/pad", "serve/launch", "serve/sync",
        "serve/readback"]
    outer, children = spans[0], spans[1:]
    assert len({e["tid"] for e in spans}) == 1
    assert outer["args"] == {"g": 4, "envelope": "x".join(
        map(str, (4, *eng.envelope(reqs[0]), "fp32")))}
    end = outer["ts"]
    for c in children:
        assert c["ts"] >= end - 1e-6  # in order, none overlapping the last
        end = c["ts"] + c["dur"]
        assert "args" not in c
    assert end <= outer["ts"] + outer["dur"] + 1e-6
    want = ScoringEngine(theta).score_batch(reqs)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def _four_array_scores(model, requests, env, g) -> list[np.ndarray]:
    """The dispatch as four separate padded arrays (user ids, user
    values, ad ids, ad values) through the plain score layer: what one
    engine dispatch of ``requests`` at (g, *env) computes."""
    ku, ka, n = env
    pad = as_model(model).num_features
    ui = np.full((g, ku), pad, np.int32)
    uv = np.zeros((g, ku), np.float32)
    ai = np.full((g * n, ka), pad, np.int32)
    av = np.zeros((g * n, ka), np.float32)
    for s, r in enumerate(requests):
        ui[s, :r.user_ids.shape[-1]] = r.user_ids
        uv[s, :r.user_vals.shape[-1]] = r.user_vals
        n_real, ka_real = r.ad_ids.shape
        ai[s * n:s * n + n_real, :ka_real] = r.ad_ids
        av[s * n:s * n + n_real, :ka_real] = r.ad_vals
    sid = np.repeat(np.arange(g, dtype=np.int32), n)
    p = np.asarray(jax.jit(lambda *a: score_bundles(model, ScoreBundle(*a)))(
        ui, uv, ai, av, sid)).reshape(g, n)
    return [p[s, :r.ad_ids.shape[0]] for s, r in enumerate(requests)]


# ------------------------------------------------ one transfer each way
@pytest.mark.parametrize("dtype", ["fp32", "int8"])
def test_packed_dispatch_bitwise_across_envelopes_and_g(theta, dtype):
    """A wavefront of mixed envelopes whose groups land on every G
    bucket: each request's packed-dispatch score is bitwise what it gets
    alone (``score``) and what the four separate padded arrays give
    through ``score_bundles`` at the same (G, envelope)."""
    model = quantize(compress(theta)) if dtype == "int8" else theta
    eng = ScoringEngine(model, k_buckets=(4, 8), n_buckets=(2, 4))
    assert eng._dtype == dtype
    shapes = [((3, 3), (2, 2), (2, 2)), ((6, 6), (3, 3), (2, 2)),
              ((3, 3), (6, 6), (3, 3)), ((7, 7), (7, 7), (4, 4)),
              ((8, 8), (3, 3), (4, 4))]
    groups = [synthetic_requests(size, num_features=D, k_user=ku, k_ad=ka,
                                 n_ads=n, seed=30 + i)
              for i, (size, (ku, ka, n)) in enumerate(
                  zip(eng.g_buckets, shapes))]
    assert len({eng.envelope(g[0]) for g in groups}) == len(groups)
    mixed = [r for g in groups for r in g]
    order = np.random.default_rng(21).permutation(len(mixed))
    wave = [mixed[i] for i in order]
    got = eng.score_batch(wave)
    assert eng.stats.dispatches == len(groups)
    assert {k[0] for k in eng.stats.bucket_hits} == set(eng.g_buckets)
    alone = ScoringEngine(model, k_buckets=(4, 8), n_buckets=(2, 4))
    by_id = {id(r): p for r, p in zip(wave, got)}
    for g, rs in zip(eng.g_buckets, groups):
        direct = _four_array_scores(model, rs, eng.envelope(rs[0]), g)
        for r, want in zip(rs, direct):
            np.testing.assert_array_equal(by_id[id(r)], want)
            np.testing.assert_array_equal(by_id[id(r)], alone.score(r))


def test_dispatch_sends_only_the_packed_buffer(theta):
    """With implicit host-to-device transfers disallowed, a warm
    dispatch still runs: its one transfer is the explicit packed one.
    The executable called on a host array is refused under the same
    guard, so the guard is live here."""
    reqs = synthetic_requests(3, num_features=D, k_user=(5, 5), k_ad=(4, 4),
                              n_ads=(3, 3), seed=22)
    eng = ScoringEngine(theta)
    eng.warm({eng.envelope(reqs[0])}, batch_sizes=eng.g_buckets)
    want = ScoringEngine(theta).score_batch(reqs)
    key = (4, *eng.envelope(reqs[0]), "fp32")
    comp = eng._executable(key)
    with jax.transfer_guard_host_to_device("disallow"):
        got = eng.score_batch(reqs)
        with pytest.raises(jax.errors.JaxRuntimeError,
                           match="host-to-device"):
            comp(eng._pad_batch(reqs, key))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert eng.stats.h2d_buffers == eng.stats.dispatches == 1


def test_one_h2d_buffer_per_dispatch_on_randomized_replay(theta):
    """Every dispatch, whatever its envelope, G or entry point, sends
    exactly one host-to-device buffer."""
    rng = np.random.default_rng(23)
    eng = ScoringEngine(theta)
    reqs = synthetic_requests(30, num_features=D, seed=24)
    for _ in range(3):
        order = rng.permutation(len(reqs))
        cut = int(rng.integers(1, len(reqs)))
        eng.score_batch([reqs[i] for i in order[:cut]])
        eng.score_many([reqs[i] for i in order[cut:cut + 3]])
    wide = tuple(max(eng.envelope(r)[i] for r in reqs[:5]) for i in range(3))
    eng.score_batch_at(reqs[:5], wide)
    s = eng.stats
    assert s.dispatches > 3
    assert s.h2d_buffers == s.dispatches
    assert s.as_dict()["h2d_buffers"] == s.dispatches


# ------------------------------------------------------- batched (G>1)
def test_score_batch_matches_score_bitwise(theta):
    """Stacking same-envelope requests into one G>1 dispatch returns the
    SAME numbers as scoring each alone: a request's padded block is
    identical either way, G slots are independent bundles."""
    reqs = synthetic_requests(20, num_features=D, seed=8)
    eng_one = ScoringEngine(theta)
    eng_many = ScoringEngine(theta)
    want = [eng_one.score(r) for r in reqs]
    got = eng_many.score_batch(reqs)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    # batching really batched: fewer dispatches than requests, G rounded
    # onto buckets (slots >= requests), every request accounted for
    b = eng_many.stats
    assert b.requests == 20 and b.dispatches < 20
    assert b.slots >= b.requests
    assert 0 < b.occupancy <= 1.0


def test_score_batch_mixed_envelopes_preserve_order(theta):
    """Requests from different envelopes come back in input order even
    though they are served by different grouped dispatches."""
    small = synthetic_requests(3, num_features=D, k_user=(4, 4), k_ad=(3, 3),
                               n_ads=(2, 2), seed=9)
    big = synthetic_requests(3, num_features=D, k_user=(20, 20), k_ad=(9, 9),
                             n_ads=(12, 12), seed=10)
    mixed = [small[0], big[0], small[1], big[1], small[2], big[2]]
    eng = ScoringEngine(theta)
    got = eng.score_batch(mixed)
    for r, p in zip(mixed, got):
        assert p.shape == (r.ad_ids.shape[0],)
        np.testing.assert_array_equal(p, ScoringEngine(theta).score(r))


def test_score_batch_splits_past_max_batch(theta):
    """A same-envelope wavefront bigger than the top G bucket splits
    into max_batch-sized chunks (scores unchanged)."""
    eng = ScoringEngine(theta, g_buckets=(1, 2, 4))
    assert eng.max_batch == 4
    reqs = synthetic_requests(11, num_features=D, k_user=(6, 6), k_ad=(4, 4),
                              n_ads=(3, 3), seed=11)
    got = eng.score_batch(reqs)
    assert eng.stats.dispatches == 3  # 4 + 4 + 3(->G=4)
    assert eng.stats.slots == 12
    for r, p in zip(reqs, got):
        np.testing.assert_array_equal(p, ScoringEngine(theta).score(r))


def test_batched_zero_recompiles_after_g_bucket_warm(theta):
    """warm(envelopes, batch_sizes=g_buckets) covers every dispatch the
    batched path can make: replays of any grouping never recompile."""
    rng = np.random.default_rng(12)
    eng = ScoringEngine(theta)
    reqs = synthetic_requests(30, num_features=D, seed=13)
    eng.warm({eng.envelope(r) for r in reqs}, batch_sizes=eng.g_buckets)
    warm = eng.stats.compiles
    for _ in range(3):
        order = rng.permutation(len(reqs))
        eng.score_batch([reqs[i] for i in order])
    eng.score_many(reqs)  # the G=1 path rides the same warmed cache
    assert eng.stats.compiles == warm, "steady state recompiled"


def test_batched_envelope_compiles_key_on_g(theta):
    """Each (G, Ku, Ka, N) key compiles exactly once: same envelope at a
    new batch size is one more compile, replays are free."""
    eng = ScoringEngine(theta, k_buckets=(8,), n_buckets=(4,),
                        g_buckets=(1, 2, 4))
    reqs = synthetic_requests(4, num_features=D, k_user=(6, 6), k_ad=(4, 4),
                              n_ads=(3, 3), seed=14)
    eng.score(reqs[0])  # (1, 8, 8, 4)
    assert eng.stats.compiles == 1
    eng.score_batch(reqs[:2])  # (2, 8, 8, 4)
    assert eng.stats.compiles == 2
    eng.score_batch(reqs)  # (4, 8, 8, 4)
    assert eng.stats.compiles == 3
    eng.score_batch(reqs[:2])  # cached
    eng.score(reqs[3])  # cached
    assert eng.stats.compiles == 3


# ------------------------------------------------- forced envelopes
def test_score_batch_at_bitwise_matches_natural_envelopes(theta):
    """Forcing a mixed wavefront onto one wide envelope (the coalesced
    dispatch primitive) returns the SAME numbers as per-envelope
    dispatch: widening only adds pad slots, which alias the zero pad
    row."""
    small = synthetic_requests(3, num_features=D, k_user=(4, 4), k_ad=(3, 3),
                               n_ads=(2, 2), seed=15)
    big = synthetic_requests(2, num_features=D, k_user=(20, 20), k_ad=(9, 9),
                             n_ads=(12, 12), seed=16)
    mixed = [small[0], big[0], small[1], big[1], small[2]]
    eng = ScoringEngine(theta)
    widest = tuple(max(eng.envelope(r)[i] for r in mixed) for i in range(3))
    got = eng.score_batch_at(mixed, widest)
    assert eng.stats.dispatches == 1  # the whole wavefront in one round
    for r, p in zip(mixed, got):
        assert p.shape == (r.ad_ids.shape[0],)
        np.testing.assert_array_equal(p, ScoringEngine(theta).score(r))


def test_score_batch_at_rejects_overflowing_requests(theta):
    reqs = synthetic_requests(2, num_features=D, k_user=(12, 12), k_ad=(6, 6),
                              n_ads=(8, 8), seed=17)
    eng = ScoringEngine(theta)
    with pytest.raises(ValueError):
        eng.score_batch_at(reqs, (8, 8, 8))  # Ku 12 > forced Ku 8


# ------------------------------------------------------- int8-native
def test_int8_engine_parity_and_dtype_keyed_cache(theta):
    """An engine built straight on a QuantizedArtifact serves int8-
    native: scores match the dequantized fp32 engine to <= 1e-6 and stay
    within |dp| <= 1e-2 of the unquantised model, while the executable
    cache keys on dtype (no sharing, no clobbering)."""
    from repro.serve import dequantize, quantize

    q = quantize(compress(theta))
    reqs = synthetic_requests(12, num_features=D, seed=18)
    eng_i8 = ScoringEngine(q)
    eng_deq = ScoringEngine(dequantize(q))
    eng_fp = ScoringEngine(theta)
    assert eng_i8._dtype == "int8" and eng_deq._dtype == "fp32"
    for r in reqs:
        p_i8 = eng_i8.score(r)
        np.testing.assert_allclose(p_i8, eng_deq.score(r),
                                   rtol=1e-6, atol=1e-6)
        assert np.abs(p_i8 - eng_fp.score(r)).max() <= 1e-2
    # batched path too
    for a, b in zip(eng_i8.score_batch(reqs), eng_deq.score_batch(reqs)):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    # dtype rides the cache key and the stats labels
    assert all(k[-1] == "int8" for k in eng_i8._compiled)
    assert all(k[-1] == "fp32" for k in eng_deq._compiled)
    assert all(k[-1] == "int8" for k in eng_i8.stats.bucket_hits)


def test_int8_engine_zero_recompiles_on_randomized_replay(theta):
    """The steady-state guarantee holds unchanged for int8-native
    engines: warm the (envelope x g_bucket) grid once, then shuffled
    replays never recompile."""
    from repro.serve import quantize

    rng = np.random.default_rng(19)
    eng = ScoringEngine(quantize(compress(theta)))
    reqs = synthetic_requests(30, num_features=D, seed=20)
    eng.warm({eng.envelope(r) for r in reqs}, batch_sizes=eng.g_buckets)
    warm = eng.stats.compiles
    first = {}
    for _ in range(3):
        order = rng.permutation(len(reqs))
        eng.score_batch([reqs[i] for i in order])
        for i in order:
            p = eng.score(reqs[i])
            if i in first:
                np.testing.assert_array_equal(p, first[i])
            else:
                first[i] = p
    assert eng.stats.compiles == warm, "int8 steady state recompiled"
