"""Kernel seconds booked to `secondary/checkpoint` (`self_sys_s`): the file
system's part of `secondary_checkpoint_s` (creating, writing, renaming and
looking up a file a cluster) against the container's (`np.savez`'s members,
the CRC). Exact where a checkpoint span outlasts `profiling.HOST_READ_EVERY_S`
(10 ms; `ru_stime` ticks at 10 ms too). Where the spans are shorter (1-2 ms a
save in the dense and greedy cells) a chunk of kernel time goes to the span
open at the next real read, so this is the kernel seconds of the whole loop
(`secondary/checkpoint`, `stage:secondary_postprocess`, `secondary/post`)
weighted by the checkpoint's share of the loop's seconds: it moves with the
loop's kernel time, not with a shift between the loop's three phases.
Median over the window's jobs; None where the record has no such field."""

from benchmark import host


def read(run: dict):
    return host.of_span(run, "secondary/checkpoint", "self_sys_s")
