"""Up plus down wire bytes per event, as the program accounts them
(``History.up_bytes + down_bytes``, the static frame sizes).

The count the paper is about.  On one chip no frame crosses a link, so
it cannot move ``events_per_s`` here: it records what a deployment would
send, and moves that rate only in a cell whose frames cross a network."""


def read(rec):
    nbytes = rec["counters"].get("wire_bytes")
    return nbytes / rec["work"] if nbytes else None
