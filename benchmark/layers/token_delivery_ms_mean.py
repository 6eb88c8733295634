"""token_delivery_ms_mean (layer: load generator) - the mean time from the
socket to the client's stamp, in milliseconds: the area between S (tokens on
the wire, a step at each `serving/write_pass`'s start) and R (tokens stamped)
over [w0, w0 + seconds], over the tokens read (`benchmark/delivery.py`).  By
Little's law that is the mean wait of a token between the two counters, with
no matching of tokens to dispatches, so it does not wrap at a dispatch period
as `token_wire_ms_p50` does: a client a second behind reads a second.  It
holds the rest of the pass that sent the token (S steps at the pass's start),
the loopback wire, the socket's buffer and the caller thread's turn at the
generator's interpreter.  None for a program whose passes carry no count
(every one before PR 54)."""

from benchmark import delivery


def read(spans, trace, run):
    got = delivery.curves(spans, run)
    return 1e3 * got["area_token_s"] / got["read"] if got and got["read"] \
        else None
