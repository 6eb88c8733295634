"""tokens_sent_per_s (layer: serving front) - the server's own rate at the
socket: the tokens of the chunk frames whose last byte the writer thread put
on a socket inside [w0, w0 + seconds], the interval `tokens_per_s` counts in,
over its seconds; from the `tokens` / `tokens_total` of the program's
`serving/write_pass` spans (`benchmark/delivery.py`).  Beside `tokens_per_s`
it says whether a cell reads the server or its load generator: what the lane
gains and the clients do not read in time shows here and not there.  None for
a program whose passes carry no count (every one before PR 54)."""

from benchmark import delivery


def read(spans, trace, run):
    got = delivery.curves(spans, run)
    return got["sent"] / got["seconds"] if got else None
