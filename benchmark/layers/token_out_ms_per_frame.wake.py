"""token_out_ms_per_frame.wake (layer: serving front) - mean milliseconds a
chunk frame spends from the lane's put of its chunk to the moment the stream's
handler thread has it (`wake_ms_sum`): the queue, and the handler's wait for
the interpreter.  The sum of the attribute over the sum of `frames` of the
program's `serving/stream_out` spans (one a request, folded in the handler
thread) that began inside the measured window.  `.lane` + `.wake` + `.send` is
the program's side of `token_wire_ms_p50`; the rest of that is the client's
read.  None for a program without the span."""

from benchmark import lane_detail


def read(spans, trace, run):
    return lane_detail.stream_out_ms_per_frame(spans, run, "wake_ms_sum")
