"""pods_per_s: pods bound inside the window over the window's span. The
window is whole rounds (run.py), so no round is cut. Host clock."""


def read(r):
    if r.cell["traffic"]["loop"] != "closed":
        return None
    return r.window_binds() / (r.t1 - r.t0)
