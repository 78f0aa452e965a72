"""copy_MiB_per_step: bytes the data model copied
(``transport_stats().bytes_copied``) from the opening of the window to the
end of the run, over the producer steps of the window.  A count."""


def read(r):
    steps = r.window_steps()
    if not steps or "bytes_copied" not in r.run.stats0:
        return None
    copied = r.stats1["bytes_copied"] - r.run.stats0["bytes_copied"]
    return copied / 2**20 / len(steps)
