"""h2d_ms_per_delivery: the consumer's ``ds[:]`` -> ``device_put`` ->
``block_until_ready``, per delivery of a window step (harness spans)."""


def read(r):
    spans = r.spans("h2d")
    if not spans:
        return None
    return sum(s.t1 - s.t0 for s in spans) / len(spans) * 1e3
