"""token_out_ms_per_frame.send (layer: serving front) - mean milliseconds a
chunk frame spends from the moment the handler thread has its chunk to the
return of its `sendall` (`send_ms_sum`): the frame's encoding and the socket
write.  The sum of the attribute over the sum of `frames` of the program's
`serving/stream_out` spans (one a request, folded in the handler thread) that
began inside the measured window.  `.lane` + `.wake` + `.send` is the
program's side of `token_wire_ms_p50`; the rest of that is the client's read.
None for a program without the span."""

from benchmark import lane_detail


def read(spans, trace, run):
    return lane_detail.stream_out_ms_per_frame(spans, run, "send_ms_sum")
