"""assemble_ms_per_step: the host assembly of the producer's sharded
snapshots, per window step: the ``datamodel.assemble`` spans ``repro.obs``
records around the copy of each fetched shard into the Dataset's buffer, on
the producer instances, in the window's steps (a traced run's program
spans).  0 where the snapshots were not sharded; nothing to read from a
program that has no ``snapshots_assembled`` counter, and so no such span."""

PRODUCER_ROLE = "write"


def read(r):
    if r.run.obs is None or "snapshots_assembled" not in r.run.stats0:
        return None
    producers = {(s.task, s.instance) for s in r.spans(PRODUCER_ROLE)}
    steps = r.window_steps()
    if not producers or not steps:
        return None
    assemble = [s["t1"] - s["t0"] for s in r.run.obs.spans()
                if s["ph"] == "X" and s["name"] == "datamodel.assemble"
                and (s["task"], s["instance"]) in producers
                and r.in_window(s["step"])]
    return sum(assemble) / len(steps) * 1e3
