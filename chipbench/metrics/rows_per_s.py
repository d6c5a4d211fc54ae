"""rows_per_s: operator rows the engine tier resolved in the window, over
the window's seconds (host clock). Counted per backend call that finished
inside the window, so a query that outlasts the window still counts its
finished morsels; a reduce is one row (one engine request)."""


def read(run):
    rows = sum(prompts for t0, t1, rows_in, prompts, kind
               in run.backend.calls if run.in_window(t1))
    return rows / run.window_s
