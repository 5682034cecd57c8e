"""Device time of the fused generate program per decode iteration (chunk
iterations per execution), from the trace."""
from benchmarks.chip.trace import module_count, module_time


def read(run):
    if not run.trace or module_count(run.trace, "generate_step") == 0:
        return None
    iters = module_count(run.trace, "generate_step") * run.work["chunk"]
    return 1e3 * module_time(run.trace, "generate_step") / iters
