"""step_ms: the producer's progress under in situ coupling.

The window runs from its opening to the last step done inside it; a step is
done when its file has closed, or, on an `all` edge, when every consumer
instance has its analysis of it on the host.  The time from the opening to
that last completion, over the number of steps done, in milliseconds."""


def read(r):
    done = sorted(t for t in r.completion().values() if t <= r.t_end)
    if not done:
        return None
    return (done[-1] - r.run.t_start) / len(done) * 1e3
