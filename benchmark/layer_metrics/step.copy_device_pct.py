"""Model step: share of the prefill programs' device time in ops that only
MOVE data: self time of the device ops that are a `copy`, a `slice` or a
`dynamic-slice`, or a fusion of nothing else (`place` below, by the kernel's
name alone), over the device time of the prefill program, summed over EVERY
`prefill_chunk` step of the window (`lib/opwalk.py` `step_seconds`).

What it is for: a layer loop reads a layer's matrices out of their stacks.
Where the slice feeds a product the compiler fuses it into the product and
the matrix is read once, in place; where it is an operand of a conditional,
a buffer of its own, the compiler copies the matrix out every trip of the
loop and the product reads it again: 197 MB a unit and a fifth of the device
in the decoder-hybrid-decoder's self half (ISSUE 49).  A relayout around a
scatter or a kernel shows here too.  A `dynamic-update-slice` (the pools'
writes) computes nothing either but writes what the step must write: it is
not counted.  0.0 where a traced window has no such op, None without a
trace.  %."""

import re

from lib import opwalk

MOVES = ("dynamic-slice", "slice", "bitcast", "copy")


def place(name, scope):
    """"copy" for an op whose kernel is named `copy`, `slice` or
    `dynamic-slice` (its trailing `.N` apart), or `<words>_fusion` where
    every word is one of MOVES (`dynamic-slice_bitcast_fusion.13` is;
    `bitcast_add_fusion.4`, `dynamic-update-slice_fusion.2` and a plain
    `fusion.7` are not), else None.  A `while` is nobody's: only self time
    is counted, so a loop's own overhead stays outside."""
    head = re.sub(r"\.\d+$", "", name.split(" = ", 1)[0].lstrip("%"))
    *words, last = head.split("_")
    if not words:
        return "copy" if last in ("copy", "slice", "dynamic-slice") else None
    if last == "fusion" and all(w in MOVES for w in words):
        return "copy"
    return None


def read(run):
    found = opwalk.step_seconds(run, place)
    if not found:
        return None
    return (100.0 * sum(g.get("copy", 0.0) for _, _, g in found)
            / sum(prog for _, prog, _ in found))
