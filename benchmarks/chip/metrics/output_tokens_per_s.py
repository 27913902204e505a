"""Output tokens emitted inside the window over the window's length."""
import timing


def read(run):
    w = run.window
    return timing.tokens_in_window(w) / (w.t1 - w.t0)
