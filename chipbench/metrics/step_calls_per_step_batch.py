"""step_calls_per_step.batch: engine-tier calls that one step of the
backend's driver served, on average over the window: the window's growth
of JAXBackend.stats["step_calls"] over that of ["steps"] (the program's
counters, copied under the backend's lock at the window's open and
close). About 1 where callers take turns on the engine; the dispatcher's
concurrency where every caller's requests share each step."""
from chipbench.measures import counter_growth


def read(run):
    steps = counter_growth(run, "backend", "steps")
    if not steps:
        return None
    return counter_growth(run, "backend", "step_calls") / steps
