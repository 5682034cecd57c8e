"""90th percentile of time to first token over every request due in the
window: from its due time in the open-loop schedule to the moment the host
holds its first token. A failed request counts as missing."""
from benchmarks.chip.record import percentile, ttft_s


def read(run):
    reqs = run.due_in_window()
    if run.kind != "serve" or not reqs:
        return None
    return 1e3 * percentile([ttft_s(r) for r in reqs], 90)
