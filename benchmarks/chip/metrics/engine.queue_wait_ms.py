"""Mean wait from a request's due time to the start of the engine step that
admitted it (the wait for a chunk boundary, and for a free slot), over the
requests due and admitted in the traced span: collecting the trace stalls
the window, and a request due just before its end waits out the stall."""


def read(run):
    end = run.traced_until if run.traced_until is not None else float("inf")
    waits = [r.admitted - r.due for r in run.due_in_trace()
             if r.admitted is not None and r.admitted < end]
    if run.kind != "serve" or not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
