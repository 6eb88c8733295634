"""client_read_share (layer: load generator) - of the tokens the server put on
the wire inside [w0, w0 + seconds], the share the generator's callers had
stamped by its end, in percent: R over S of `benchmark/delivery.py`, at most
100 because a token is stamped after the pass that sent it began.  100 minus
it is the share of the server's work that `tokens_per_s` does not see.  A cell
that reads its server holds about one delivery in flight at the window's end
(99.5% and more); a generator at its ceiling leaves what the lane gains in the
socket buffers, and the share falls with every gain.  None for a program whose
`serving/write_pass` carries no `tokens` (every one before PR 54), and where
the two counts disagree (`delivery_inconsistent`)."""

from benchmark import delivery


def read(spans, trace, run):
    got = delivery.curves(spans, run)
    return 100.0 * got["read"] / got["sent"] if got and got["sent"] else None
