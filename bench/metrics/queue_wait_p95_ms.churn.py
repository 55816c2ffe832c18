"""95th percentile (ms) of the time reads waited in the server's queue:
the program's own admission and dispatch stamps on each request."""

from bench import measures


def read(run):
    return measures.percentile(
        [(r["t_dispatch"] - r["t_submit_server"]) * 1e3
         for r in run.reads if r.get("t_dispatch") is not None], 95)
