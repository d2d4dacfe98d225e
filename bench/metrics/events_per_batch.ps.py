"""Events per dispatched batch in the window: the event loop's batching
(``async_sim.batch_schedule`` over the window's schedule)."""


def read(rec):
    batches = rec["counters"].get("batches")
    return rec["work"] / batches if batches else None
