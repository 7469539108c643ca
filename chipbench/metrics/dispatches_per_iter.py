"""Engine dispatches per iteration: ``EngineReport.dispatches`` summed over
the window, over the iterations completed in it (a count the program makes)."""


def read(w):
    return w.dispatches / w.iterations if w.iterations else None
