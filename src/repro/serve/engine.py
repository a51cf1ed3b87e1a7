"""Batched online scoring engine: bucketed shapes, cached executables.

Online traffic is ragged: every page view carries its own user id count
Ku, per-candidate id count Ka and candidate count N. JAX compiles per
shape, so scoring raw shapes would recompile on nearly every request —
the latency cliff production scorers cannot afford. The engine lands the
ROADMAP "bucketed shape padding" idea on the serving side:

  * each request is padded up to a bucketed ENVELOPE (K_user, K_ad, N)
    (pad slots carry the pad id with value 0, padded candidates are
    sliced off the result);
  * same-envelope requests STACK: :meth:`ScoringEngine.score_batch`
    groups a wavefront of requests by envelope and serves each group as
    ONE ``G > 1`` bundle call (G itself bucketed, pad bundles are all-pad
    and sliced off), so the per-dispatch overhead — python padding,
    executable launch, device sync — amortises over G page views. This
    is the traffic-shaped fast path the micro-batching queue
    (``repro.serve.traffic``) flushes into;
  * per (G, K_user, K_ad, N, dtype) envelope the scoring executable is
    AOT-compiled ONCE (``jit(...).lower(...).compile()``) and cached
    (dtype is "fp32" or "int8" — an int8-native engine's executables
    run the scale-fused int8 gather path and never collide with fp32
    ones on the same shapes);
    envelope keys are the ONLY source of compilation, so once the bucket
    set is warm a request replay of any mix/order/grouping triggers ZERO
    recompiles (asserted in ``tests/test_serve_engine.py``). An AOT
    executable also cannot silently retrace — a shape bug raises instead
    of recompiling.

Scoring runs the session-shared path (``serve.score.score_bundles``,
Eq. 13): each request's user contraction happens once and broadcasts
over its padded candidate block; a batched call carries G independent
user rows and G*N candidates. The model (full Theta, a pruned
:class:`~repro.serve.compress.ServingArtifact`, or an int8
:class:`~repro.serve.compress.QuantizedArtifact` — served INT8-NATIVE:
the executables run the scale-fused int8 gather, fp32 rows are never
materialised) is normalised and placed on device once at engine
construction; requests stay in the original id space either way.

:class:`EngineStats` keeps the latency/throughput ledger: request and
candidate counts, dispatch (AOT call) and padded-slot counts with the
implied batch occupancy, per-envelope hit counts, compile count and
seconds, and scoring wall seconds (used by ``benchmarks/bench_serve.py``
and the ``repro.launch.serve`` smoke).

One dispatch makes one transfer each way. The padded batch is ONE
int32 buffer, ``[user ids | user values | ad ids | ad values]`` with the
value blocks written through float32 views of it, so it crosses to the
device as one explicit ``jax.device_put``; the executable slices it
back into the four arrays in-program (a bitcast, so scores are bitwise
what four separate arrays give). The scores' copy to the host is
started (``copy_to_host_async``) as soon as the round is enqueued, so
it runs when the round ends instead of after the host sees the end.

With a tracer on (``repro.obs``), each dispatch is one ``serve/dispatch``
span with four children in order: ``serve/pad`` (the packed numpy
buffer), ``serve/launch`` (the one packed transfer, the executable call
up to its return and the start of the scores' copy to the host),
``serve/sync`` (``block_until_ready``: the wait for the round) and
``serve/readback`` (``np.asarray`` of the copy already under way, the
reshape and the per-request slices).
"""
from __future__ import annotations

import time
from contextlib import contextmanager
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.serve.score import ScoreBundle, as_model, score_bundles
from repro.tune import round_up

# default bucket edges; above the top edge, round up to a multiple of it.
# K edges are dense at the small end (production id lists are tens),
# N edges cover typical candidate-slate sizes, G edges the micro-batch
# sizes the queue flushes (powers of two so a handful of executables
# covers every flush size).
DEFAULT_K_BUCKETS = (8, 16, 24, 32, 48, 64)
DEFAULT_N_BUCKETS = (4, 8, 16, 32, 64)
DEFAULT_G_BUCKETS = (1, 2, 4, 8, 16)


class BundleRequest(NamedTuple):
    """One page view: a user id list + N candidate id lists (original id
    space, no padding — the engine pads)."""

    user_ids: np.ndarray  # (Ku,) int
    user_vals: np.ndarray  # (Ku,) float
    ad_ids: np.ndarray  # (N, Ka) int
    ad_vals: np.ndarray  # (N, Ka) float


class EngineStats:
    """Serving counters (one labeled family per engine) — a view over the
    process metrics registry: every field reads back out of a registry
    series, so the same numbers export through ``--metrics-out`` while
    the attribute/property API (and ``as_dict``) stays exactly as it was.

    ``h2d_buffers`` (series ``serve_h2d_buffers``) counts the host-to-
    device buffers the dispatches sent: one packed buffer a dispatch, so
    it equals ``dispatches`` (four separate argument arrays would make it
    four times that).
    """

    def __init__(self, registry=None):
        reg = registry if registry is not None else obs.get_registry()
        labels = {"engine": obs.next_instance("engine")}
        self._reg, self._labels = reg, labels
        self._requests = reg.counter("serve_requests", **labels)
        self._candidates = reg.counter("serve_candidates", **labels)
        self._dispatches = reg.counter("serve_dispatches", **labels)
        self._slots = reg.counter("serve_slots", **labels)
        self._h2d_buffers = reg.counter("serve_h2d_buffers", **labels)
        self._compiles = reg.counter("serve_compiles", **labels)
        self._compile_s = reg.counter("serve_compile_seconds", **labels)
        self._score_s = reg.counter("serve_score_seconds", **labels)
        self._wall_hist = reg.histogram("serve_dispatch_wall_seconds",
                                        **labels)
        self._hits: dict[tuple, obs.Counter] = {}

    # ------------------------------------------------------------- mutators
    def note_compile(self, seconds: float) -> None:
        self._compiles.inc(1.0)
        self._compile_s.inc(seconds)

    def note_dispatch(self, key: tuple, requests: int,
                      candidates: int, wall_s: float,
                      h2d_buffers: int) -> None:
        """Book one AOT executable call: its padded envelope, the real
        requests/candidates it carried, its wall time and the host-to-
        device buffers it sent."""
        self._score_s.inc(wall_s)
        self._wall_hist.observe(wall_s)
        self._dispatches.inc(1.0)
        self._h2d_buffers.inc(float(h2d_buffers))
        self._slots.inc(float(key[0]))
        self._requests.inc(float(requests))
        self._candidates.inc(float(candidates))
        hit = self._hits.get(key)
        if hit is None:
            hit = self._reg.counter("serve_bucket_hits",
                                    envelope="x".join(map(str, key)),
                                    **self._labels)
            self._hits[key] = hit
        hit.inc(float(requests))

    # ---------------------------------------------------------------- views
    @property
    def requests(self) -> int:
        return int(self._requests.value)

    @property
    def candidates(self) -> int:
        return int(self._candidates.value)

    @property
    def dispatches(self) -> int:
        return int(self._dispatches.value)

    @property
    def slots(self) -> int:
        return int(self._slots.value)

    @property
    def h2d_buffers(self) -> int:
        return int(self._h2d_buffers.value)

    @property
    def compiles(self) -> int:
        return int(self._compiles.value)

    @property
    def compile_seconds(self) -> float:
        return self._compile_s.value

    @property
    def score_seconds(self) -> float:
        return self._score_s.value

    @property
    def bucket_hits(self) -> dict[tuple, int]:
        return {k: int(c.value) for k, c in self._hits.items()}

    @property
    def latency_us(self) -> float:
        """Mean per-request scoring wall time (padding + device + sync);
        batched requests share their dispatch's wall time."""
        return self.score_seconds / self.requests * 1e6 if self.requests else 0.0

    @property
    def candidates_per_sec(self) -> float:
        return self.candidates / self.score_seconds if self.score_seconds else 0.0

    @property
    def occupancy(self) -> float:
        """Real requests per padded bundle slot (1.0 = no G padding)."""
        return self.requests / self.slots if self.slots else 0.0

    def as_dict(self) -> dict:
        return {
            "requests": self.requests,
            "candidates": self.candidates,
            "dispatches": self.dispatches,
            "slots": self.slots,
            "h2d_buffers": self.h2d_buffers,
            "occupancy": self.occupancy,
            "compiles": self.compiles,
            "compile_seconds": self.compile_seconds,
            "score_seconds": self.score_seconds,
            "latency_us": self.latency_us,
            "candidates_per_sec": self.candidates_per_sec,
            "bucket_hits": {"x".join(map(str, k)): v
                            for k, v in self.bucket_hits.items()},
        }


def _packed_size(key: tuple) -> int:
    """Length of the int32 buffer one dispatch at ``key`` sends."""
    g, ku, ka, n = key[:4]
    return 2 * g * ku + 2 * g * n * ka


def _unpack(packed, key: tuple, as_f32):
    """The four padded arrays of a packed dispatch buffer, in order: user
    ids (g, ku), user values (g, ku), ad ids (g*n, ka), ad values
    (g*n, ka). The value blocks hold float32 bits; ``as_f32`` reads them
    as float32 without changing a bit (a numpy view on the host, a
    bitcast in the program)."""
    g, ku, ka, n = key[:4]
    u, a = g * ku, g * n * ka
    return (packed[:u].reshape(g, ku),
            as_f32(packed[u:2 * u]).reshape(g, ku),
            packed[2 * u:2 * u + a].reshape(g * n, ka),
            as_f32(packed[2 * u + a:]).reshape(g * n, ka))


# The engine's envelope rounding and the autotune table's shape buckets
# share ONE rule: a request padded to its engine bucket lands on the
# same table envelope every time, so block-size resolution is as
# recompile-free as the executable cache itself.
_round_up = round_up


class ScoringEngine:
    """Steady-state no-recompile bundle scorer (see module docstring)."""

    def __init__(self, model, *, mode: str = "auto", dedup: bool = True,
                 k_buckets: Sequence[int] = DEFAULT_K_BUCKETS,
                 n_buckets: Sequence[int] = DEFAULT_N_BUCKETS,
                 g_buckets: Sequence[int] = DEFAULT_G_BUCKETS):
        self._model = as_model(model)  # arrays are already device-resident
        self._mode = mode
        self._dedup = dedup
        self._k_buckets = tuple(sorted(k_buckets))
        self._n_buckets = tuple(sorted(n_buckets))
        self._g_buckets = tuple(sorted(g_buckets))
        self._pad_id = self._model.num_features  # original-space pad id
        # executables key on the model dtype too: an int8-native engine
        # and an fp32 engine never share (or clobber) a cache entry even
        # when their envelopes coincide, and the dtype rides the stats/
        # ledger envelope labels
        self._dtype = "int8" if self._model.is_int8 else "fp32"
        self._compiled: dict[tuple, jax.stages.Compiled] = {}
        self.stats = EngineStats()
        self._dispatch_ctx = ("direct", 0.0)  # (flush reason, queue delay us)

    @property
    def g_buckets(self) -> tuple[int, ...]:
        """The batch-size bucket edges dispatches round G up to."""
        return self._g_buckets

    @property
    def max_batch(self) -> int:
        """Largest bundle count one dispatch carries (top G bucket);
        bigger wavefronts split into chunks of this size."""
        return self._g_buckets[-1]

    # ------------------------------------------------------------ envelopes
    def envelope(self, request: BundleRequest) -> tuple[int, int, int]:
        """The (K_user, K_ad, N) bucket this request is served under."""
        ku = _round_up(request.user_ids.shape[-1], self._k_buckets)
        ka = _round_up(request.ad_ids.shape[-1], self._k_buckets)
        n = _round_up(request.ad_ids.shape[0], self._n_buckets)
        return ku, ka, n

    def _executable(self, key: tuple):
        comp = self._compiled.get(key)
        if comp is None:
            g, n = key[0], key[3]
            model, mode, dedup = self._model, self._mode, self._dedup

            def fn(packed):
                ui, uv, ai, av = _unpack(
                    packed, key, lambda x: lax.bitcast_convert_type(x, jnp.float32))
                bundle = ScoreBundle(
                    ui, uv, ai, av, jnp.repeat(jnp.arange(g, dtype=jnp.int32), n))
                return score_bundles(model, bundle, mode=mode, dedup=dedup)

            t0 = time.perf_counter()
            with obs.get_tracer().span("serve/compile",
                                       envelope="x".join(map(str, key))):
                comp = jax.jit(fn).lower(
                    jax.ShapeDtypeStruct((_packed_size(key),), jnp.int32),
                ).compile()
            self.stats.note_compile(time.perf_counter() - t0)
            self._compiled[key] = comp
        return comp

    @contextmanager
    def dispatch_context(self, flush_reason: str, queue_delay_us: float):
        """Attribute the dispatches inside this scope to a micro-batch
        flush (``repro.serve.traffic`` wraps its drains in this so the
        ``serve_dispatch`` ledger records carry the flush reason and the
        oldest-request queue delay; un-wrapped calls book as "direct")."""
        prev = self._dispatch_ctx
        self._dispatch_ctx = (flush_reason, float(queue_delay_us))
        try:
            yield
        finally:
            self._dispatch_ctx = prev

    def warm(self, envelopes: Sequence[tuple[int, int, int]], *,
             batch_sizes: Sequence[int] = (1,)) -> None:
        """Precompile a bucket set (deploy-time, off the request path).

        ``batch_sizes`` are the G buckets to warm per (Ku, Ka, N)
        envelope — pass the engine's ``g_buckets`` when the traffic will
        arrive through :meth:`score_batch` / the micro-batching queue,
        whose flush sizes round onto exactly those buckets.
        """
        for ku, ka, n in envelopes:
            for g in batch_sizes:
                self._executable((_round_up(g, self._g_buckets), ku, ka, n,
                                  self._dtype))

    # -------------------------------------------------------------- scoring
    def _pad_batch(self, requests: Sequence[BundleRequest],
                   key: tuple) -> np.ndarray:
        """Stack same-envelope requests into the padded batch layout:
        request s owns user row s and candidate rows [s*n, (s+1)*n); pad
        candidate rows and pad bundle slots are all-pad-id (their scores
        come out 0.5 and are sliced off).

        The four arrays are views of ONE fresh int32 buffer (``_unpack``),
        the values written through float32 views, so the batch crosses to
        the device as one transfer."""
        n = key[3]
        packed = np.zeros(_packed_size(key), np.int32)  # zero bits = 0.0f
        ui, uv, ai, av = _unpack(packed, key, lambda x: x.view(np.float32))
        ui.fill(self._pad_id)
        ai.fill(self._pad_id)
        for s, r in enumerate(requests):
            ui[s, :r.user_ids.shape[-1]] = r.user_ids
            uv[s, :r.user_vals.shape[-1]] = r.user_vals
            n_real, ka_real = r.ad_ids.shape
            ai[s * n:s * n + n_real, :ka_real] = r.ad_ids
            av[s * n:s * n + n_real, :ka_real] = r.ad_vals
        return packed

    def _score_chunk(self, requests: Sequence[BundleRequest],
                     env: tuple[int, int, int]) -> list[np.ndarray]:
        """One dispatch: requests fitting ``env``, len <= max_batch."""
        ku, ka, n = env
        key = (_round_up(len(requests), self._g_buckets), ku, ka, n,
               self._dtype)
        comp = self._executable(key)  # compile time books separately
        tracer = obs.get_tracer()
        args = ({"g": key[0], "envelope": "x".join(map(str, key))}
                if tracer.enabled else {})
        t0 = time.perf_counter()
        with tracer.span("serve/dispatch", **args):
            with tracer.span("serve/pad"):
                packed = self._pad_batch(requests, key)
            with tracer.span("serve/launch"):
                # one transfer in, the enqueue, and the copy out started
                # now so it runs as soon as the round ends
                p = comp(jax.device_put(packed))
                p.copy_to_host_async()
            with tracer.span("serve/sync"):
                p = jax.block_until_ready(p)
            with tracer.span("serve/readback"):
                p = np.asarray(p).reshape(key[0], n)
                out = [p[s, :r.ad_ids.shape[0]]
                       for s, r in enumerate(requests)]
        wall = time.perf_counter() - t0
        n_cands = sum(r.ad_ids.shape[0] for r in requests)
        self.stats.note_dispatch(key, len(requests), n_cands, wall,
                                 h2d_buffers=1)
        led = obs.get_ledger()
        if led.enabled:
            reason, qdelay = self._dispatch_ctx
            led.emit(
                "serve_dispatch", envelope=list(key), g=key[0],
                requests=len(requests), candidates=n_cands,
                occupancy=len(requests) / key[0], wall_s=wall,
                flush_reason=reason, queue_delay_us=qdelay)
        mon = obs.get_monitor()
        if mon.enabled:
            mon.observe_dispatch(out, requests)
        return out

    def score(self, request: BundleRequest) -> np.ndarray:
        """p(y=1|x) for each of the request's N candidates, in order
        (a G=1 dispatch)."""
        return self._score_chunk([request], self.envelope(request))[0]

    def score_batch(self, requests: Sequence[BundleRequest]) -> list[np.ndarray]:
        """Score a wavefront of requests, batching same-envelope ones
        into G>1 dispatches (groups bigger than ``max_batch`` split).

        Returns per-request score vectors in the INPUT order; the
        scores are exactly what :meth:`score` returns for each request
        alone (same envelope padding, same kernel — asserted in tests
        and ``benchmarks/bench_serve.py``), the win is dispatch count.
        """
        results: list[np.ndarray | None] = [None] * len(requests)
        groups: dict[tuple[int, int, int], list[int]] = {}
        for i, r in enumerate(requests):
            groups.setdefault(self.envelope(r), []).append(i)
        cap = self.max_batch
        for env, idxs in groups.items():
            for s in range(0, len(idxs), cap):
                chunk = idxs[s:s + cap]
                scores = self._score_chunk([requests[i] for i in chunk], env)
                for i, p in zip(chunk, scores):
                    results[i] = p
        return results  # type: ignore[return-value]

    def score_batch_at(self, requests: Sequence[BundleRequest],
                       env: tuple[int, int, int]) -> list[np.ndarray]:
        """Score a wavefront at ONE caller-chosen envelope every request
        must fit — the micro-batching queue's cross-envelope COALESCED
        flush path: several small same-deadline groups ride one device
        round at the widest due envelope instead of one round each.

        Scores are bitwise what per-envelope dispatch returns: widening
        a request's envelope only adds pad-id slots, which alias the
        zero pad row and contribute exact zeros to its per-sample
        contraction (pad candidate rows are sliced off). Wavefronts
        bigger than ``max_batch`` split in input order.
        """
        ku, ka, n = env
        for r in requests:
            if (r.user_ids.shape[-1] > ku or r.ad_ids.shape[-1] > ka
                    or r.ad_ids.shape[0] > n):
                raise ValueError(
                    f"request (Ku={r.user_ids.shape[-1]}, "
                    f"Ka={r.ad_ids.shape[-1]}, N={r.ad_ids.shape[0]}) "
                    f"does not fit envelope {env}")
        out: list[np.ndarray] = []
        for s in range(0, len(requests), self.max_batch):
            out += self._score_chunk(requests[s:s + self.max_batch], env)
        return out

    def score_many(self, requests: Sequence[BundleRequest]) -> list[np.ndarray]:
        """One-request-at-a-time replay (the un-batched baseline;
        ``score_batch`` is the traffic-shaped path)."""
        return [self.score(r) for r in requests]


def envelope_closure(
        envelopes: Sequence[tuple[int, int, int]]
) -> set[tuple[int, int, int]]:
    """Close an envelope set under elementwise max: the cross product of
    observed component values. A coalesced flush dispatches at the
    elementwise max of its member envelopes, which always lands in this
    closure — warm it (with ``batch_sizes=g_buckets``) and coalesced
    traffic keeps the zero-steady-state-recompile guarantee."""
    envs = list(envelopes)
    if not envs:
        return set()
    kus = {e[0] for e in envs}
    kas = {e[1] for e in envs}
    ns = {e[2] for e in envs}
    return {(ku, ka, n) for ku in kus for ka in kas for n in ns}


def synthetic_requests(num: int, *, num_features: int,
                       k_user: tuple[int, int] = (12, 24),
                       k_ad: tuple[int, int] = (6, 12),
                       n_ads: tuple[int, int] = (10, 30),
                       seed: int = 0) -> list[BundleRequest]:
    """Ragged random request traffic for tests/benches/smokes: every
    request draws its own Ku, Ka and N uniformly from the given ranges
    (inclusive), ids uniform over the ORIGINAL feature space."""
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(num):
        ku = int(rng.integers(k_user[0], k_user[1] + 1))
        ka = int(rng.integers(k_ad[0], k_ad[1] + 1))
        n = int(rng.integers(n_ads[0], n_ads[1] + 1))
        out.append(BundleRequest(
            user_ids=rng.integers(0, num_features, (ku,)).astype(np.int32),
            user_vals=(rng.normal(size=(ku,)) / np.sqrt(ku)).astype(np.float32),
            ad_ids=rng.integers(0, num_features, (n, ka)).astype(np.int32),
            ad_vals=(rng.normal(size=(n, ka)) / np.sqrt(ka)).astype(np.float32),
        ))
    return out
