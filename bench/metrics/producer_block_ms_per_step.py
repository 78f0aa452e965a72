"""producer_block_ms_per_step: the producer instances' ``block`` time in the
window, per window step, from the ``repro.obs`` spans of a traced run.

``block`` is ``repro.obs.critical``'s first bucket, copied here: the union
of ``channel.*`` spans and ``vol.*.wait`` spans of the instance (the
rendezvous in ``Channel.offer``), clipped to the window."""

PRODUCER_ROLE = "write"


def read(r):
    if r.run.obs is None:
        return None
    producers = {(s.task, s.instance) for s in r.spans(PRODUCER_ROLE)}
    steps = r.window_steps()
    if not producers or not steps:
        return None
    window = (r.run.t_start, r.run.t_stop)
    ivs = []
    for s in r.run.obs.spans():
        if s["ph"] != "X" or (s["task"], s["instance"]) not in producers:
            continue
        if s["cat"] == "channel" or (s["cat"] == "vol"
                                     and s["name"].endswith(".wait")):
            a, b = max(s["t0"], window[0]), min(s["t1"], window[1])
            if b > a:
                ivs.append((a, b))
    blocked, end = 0.0, window[0]
    for a, b in sorted(ivs):
        a = max(a, end)
        if b > a:
            blocked += b - a
            end = b
    return blocked / len(steps) * 1e3
