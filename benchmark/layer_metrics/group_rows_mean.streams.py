"""Mean rows that frames brought to a scheduler group at the LM stage over
the window: the `group_frames:lm` histogram's mean times the rows of a
frame.  32 when every group is full; the program's own `group_rows`
histogram records the padded size, which is 32 whatever the group held."""


def read(run):
    count, total = (run.counters or {}).get("group_frames:lm", (0, 0.0))
    return total / count * run.rows_per_frame if count else None
