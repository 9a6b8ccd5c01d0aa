"""Model FLOP utilization of the whole window: the required operations of
the window's prefills and decode steps (``work.py``: every row up to its
exit, with its exit heads and backfill) over window seconds times the
chip's peak bf16 rate, in percent."""


def read(r):
    if r.peaks is None or not (r.prefills or r.lane_steps):
        return None
    flops = sum(r.work.prefill_flops(r.sizes, rows, seq)
                for _, rows, seq in r.prefills)
    flops += sum(r.work.decode_step_flops(r.sizes, e, c)
                 for e, c in r.lane_steps)
    return 100.0 * flops / (r.window_s * r.peaks["bf16_flops_per_s"])
