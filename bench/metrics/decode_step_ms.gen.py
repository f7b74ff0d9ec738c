"""Mean host time of a decode step (serve step through the tokens' copy to
the host), over the steps of the measured window: their summed time over
their count. Moves tokens_per_s."""

from bench.readers import in_window


def read(run):
    st = run.records["steps"]
    sel = in_window(run.records, st["t_s"], st["t_e"])
    if not sel.any():
        return None
    return 1e3 * float((st["t_e"][sel] - st["t_s"][sel]).sum()) / int(sel.sum())
