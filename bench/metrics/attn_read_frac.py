"""Model step: share of the reserved KV rows that decode attention read,
over the window: growth of the engine counter ``attn_rows_read`` (each
slot's live rows in whole blocks where the decode kernel engages, its whole
reservation where it does not) over the growth of ``attn_rows_reserved``
(max_batch x cache_len a decode step).  None for a program that keeps no
such counters, or a window with no decode step."""


def read(run):
    if "attn_rows_read" not in run.served.counters_open:
        return None
    reserved = run.counter_delta("attn_rows_reserved")
    if not reserved:
        return None
    return run.counter_delta("attn_rows_read") / reserved
