"""The program's own record of each batch of the window.

Each answered query handle holds ``batch``, what its batch did (the
served path's ``BatchRecord``): its number, when it left admission and
when its device work was enqueued (on the service's clock), and what its
scan saw.  The handles are those of the window's queries, so the records
are the window's and no other.  A program without such a record gives
none, and the readers built on it read nothing.
"""


def records(run) -> list:
    """One record per batch of the window, in submit order."""
    seen = {}
    for b in run.batches:
        for h in b.handles:
            rec = getattr(h, "batch", None)
            if rec is not None:
                seen[rec.seq] = rec
    return [seen[k] for k in sorted(seen)]


def steady(run) -> list:
    """The records of the batches that answer the window's steady state:
    those with no query submitted before the window's first answer.  The
    buckets that fill the closed loop at the window's start find the
    engine idle or queue behind each other, and a mean that took them in
    would move with how many batches the window holds.  The harness's
    submit and answer times are both on its clock (``time.perf_counter``).
    """
    recs = records(run)
    if not recs:
        return []
    first = min(b.t_done for b in run.batches)
    early = {h.batch.seq for b in run.batches if b.t_submit < first
             for h in b.handles if getattr(h, "batch", None) is not None}
    return [r for r in recs if r.seq not in early]
