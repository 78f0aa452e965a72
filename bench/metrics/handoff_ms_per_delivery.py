"""handoff_ms_per_delivery: from the producer's ``channel.offer`` span of a
window step to the last end of the consumer's spans of the same hand-off
(``vol.open.wait``, ``channel.get``, ``prefetch.wait``: the spans that carry
its flow id), per delivery: the channel's part of the staleness, read from
a traced run's ``repro.obs`` spans."""


def read(r):
    if r.run.obs is None:
        return None
    offers = {}
    taken = {}
    for s in r.run.obs.spans():
        flow = s.get("flow")
        if s["ph"] != "X" or flow is None:
            continue
        role, fid = flow
        if role == "s" and s["name"] == "channel.offer":
            if r.in_window(s["step"]):
                offers[fid] = s["t1"]
        elif s["task"] != "pool":
            taken[fid] = max(taken.get(fid, s["t1"]), s["t1"])
    lat = [taken[f] - t for f, t in offers.items() if f in taken]
    if not lat:
        return None
    return sum(lat) / len(lat) * 1e3
