"""Latents delivered inside the window / the window's seconds (closed
backlog cells; in an open-loop cell the rate is the offered load)."""


def read(rec):
    w = rec.window
    if not w.closed_loop:
        return None
    return len(w.done) / (w.t1 - w.t0)
