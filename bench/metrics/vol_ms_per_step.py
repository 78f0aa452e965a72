"""vol_ms_per_step: the producer's ``with h5.File(..., "w")`` block --
create_dataset (which snapshots the field to the host) and close (the VOL's
serve into the channels) -- summed over the window steps, per step."""


def read(r):
    spans = r.spans("write")
    if not spans:
        return None
    return sum(s.t1 - s.t0 for s in spans) / len(spans) * 1e3
