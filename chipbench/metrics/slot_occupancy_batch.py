"""slot_occupancy.batch: mean per cent of the engine's slots that were
live over the window's decode ticks (the engine's own per-tick record,
the same quantity as GenerationEngine.occupancy)."""
from chipbench.measures import window_ticks


def read(run):
    ticks = window_ticks(run)
    if not ticks:
        return None
    return 100.0 * sum(live for live, _ in ticks) / (
        len(ticks) * run.engine.n_slots)
