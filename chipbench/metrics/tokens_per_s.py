"""tokens_per_s: output tokens that reached the host inside the window,
over the window's length (tokens/s)."""


def read(rec):
    end = rec["seconds"]
    n = sum(1 for r in rec["requests"] for t in r["times"] if 0.0 <= t <= end)
    return n / end
