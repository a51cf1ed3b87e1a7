"""serve_occupancy: real requests per padded bundle slot over the traced
window's dispatches (``EngineStats`` requests / slots). Moves
``serve_p50_ms``."""


def read(x):
    c = x["counters"]
    return c["requests"] / c["slots"] if c.get("slots") else None
