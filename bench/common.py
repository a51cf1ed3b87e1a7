"""Helpers the drivers share: weights from the seed, the compile
counter, the device's memory peak."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any seed in [0, 2**64): both 32-bit halves count
    (``jax.random.key`` keeps only the low 32 bits without x64)."""
    return jax.random.wrap_key_data(np.asarray(
        [(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32))


@functools.partial(jax.jit, static_argnames=("shape", "scale"))
def _normal(key, shape, scale):
    return scale * jax.random.normal(key, shape, jnp.float32)


def init_theta(seed: int, d: int, m2: int, scale: float = 0.01) -> jax.Array:
    """Theta0 ~ scale * N(0, 1), made on the device in one jitted call."""
    return _normal(seed_key(seed), (d, m2), scale)


class CompileCounter:
    """Counts XLA compiles (persistent-cache hits included) and cache
    hits while active."""

    def __init__(self):
        self.compiles = 0
        self.cache_hits = 0
        self._on = False
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, _secs, **_kw):
        if self._on and event == _COMPILE_EVENT:
            self.compiles += 1

    def _event(self, event, **_kw):
        if self._on and event == _CACHE_HIT_EVENT:
            self.cache_hits += 1

    def __enter__(self):
        self._on = True
        return self

    def __exit__(self, *exc):
        self._on = False


def memory_peak_bytes(devices) -> int:
    """Peak bytes in use on the fullest of ``devices`` (0 if unknown)."""
    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for dev in devices]
    return int(max(peaks)) if peaks else 0
