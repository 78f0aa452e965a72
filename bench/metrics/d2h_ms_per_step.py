"""d2h_ms_per_step: the device->host fetch of the producer's snapshots, per
window step: the ``datamodel.d2h`` spans ``repro.obs`` records around
``np.asarray`` of a device array in ``Dataset.__init__``, on the producer
instances, in the window's steps (a traced run's program spans)."""

PRODUCER_ROLE = "write"


def read(r):
    if r.run.obs is None:
        return None
    producers = {(s.task, s.instance) for s in r.spans(PRODUCER_ROLE)}
    steps = r.window_steps()
    if not producers or not steps:
        return None
    d2h = [s["t1"] - s["t0"] for s in r.run.obs.spans()
           if s["ph"] == "X" and s["name"] == "datamodel.d2h"
           and (s["task"], s["instance"]) in producers
           and r.in_window(s["step"])]
    if not d2h:
        return None
    return sum(d2h) / len(steps) * 1e3
