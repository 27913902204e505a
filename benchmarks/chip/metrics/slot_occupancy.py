"""Live rows over slots per decode call, averaged over the window's
decode calls (the engine tracer's ``decode_step`` spans), in percent."""


def read(run):
    w = run.window
    live = [args["live"] for name, tid, t0, t1, args in run.spans
            if name == "decode_step" and w.t0 <= t0 and t1 <= w.t1]
    if not live:
        return None
    return 100.0 * sum(live) / (len(live) * run.slots)
