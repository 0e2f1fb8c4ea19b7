"""Host clock: the whole window over the calls it completed.  The window
ends at the first call that returns at or after --seconds, so no call is
partial; the client's right-hand-side generation is inside the window."""


def read(run):
    return run.window_s / len(run.calls) if run.calls else None
