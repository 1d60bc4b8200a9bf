"""The summed latency of the requests completed in the window over their
number (host clock)."""


def read(r):
    xs = [s.t_done - s.t_send for s in r.sent
          if s.error is None and s.t_done is not None
          and r.in_window(s.t_done)]
    return 1e3 * sum(xs) / len(xs) if xs else None
