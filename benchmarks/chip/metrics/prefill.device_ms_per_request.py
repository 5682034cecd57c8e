"""Device time inside the prefill program per execution (one request each),
from the trace."""
from benchmarks.chip.trace import module_count, module_time


def read(run):
    if not run.trace or module_count(run.trace, "prefill_step") == 0:
        return None
    return 1e3 * (module_time(run.trace, "prefill_step")
                  / module_count(run.trace, "prefill_step"))
