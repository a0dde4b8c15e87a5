"""Share of the devices' busy time spent in collectives, %: the union of
the collective operations' runs in the traced window (all-reduce,
all-gather, reduce-scatter, collective-permute and all-to-all, their
start and done parts, the fusions that call them and the asynchronous
collective calls), summed over the cell's devices, over the busy time
summed over them."""

import re

from bench.tracing import union_length

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
         "all-to-all")
_ALT = "|".join(KINDS)
# the opcode after the output shapes, a fusion calling a collective, or a
# collective issued as an asynchronous custom call
COLLECTIVE = re.compile(
    rf"^%\S+ = .*?\s(?:{_ALT})(?:-start|-done)?\("
    rf"|, calls=%(?:{_ALT})"
    r'|custom_call_target="AsyncCollective')


def is_collective(op) -> bool:
    return op.name.startswith(KINDS) or bool(COLLECTIVE.search(op.text))


def read(ctx):
    t = ctx.trace_data
    if t is None:
        return None
    busy = coll = 0.0
    for ops in t.ops:
        busy += union_length([(o.start, o.end) for o in ops], t.window)
        coll += union_length([(o.start, o.end) for o in ops if is_collective(o)],
                             t.window)
    if busy <= 0:
        return None
    return 100.0 * coll / busy
