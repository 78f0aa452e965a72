"""d2h_MiB_per_step: bytes the data model fetched from device arrays
(``transport_stats().bytes_d2h``) from the opening of the window to its
close, over the producer steps of the window.  A count."""


def read(r):
    steps = r.window_steps()
    if not steps or "bytes_d2h" not in r.run.stats0:
        return None
    fetched = r.stats1["bytes_d2h"] - r.run.stats0["bytes_d2h"]
    return fetched / 2**20 / len(steps)
