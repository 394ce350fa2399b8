"""Milliseconds a traced decode step of the device time of some op
families: the seconds, over the traced window, of every op family
(`trace_reduce.op_family`) that contains all of `match` and none of
`exclude`, or that one of `also` (further match lists) takes, over the
decode steps the kind counted while the profiler ran. For work that is
XLA's own operations and not a named kernel: a family is an operation's
NAME, not the scope it was traced under, so whatever else the traced
seconds run under the same name is in the number (the metric's `note`
says what).

params: match, exclude, also. Reads the observation `traced_ops`
(`kinds/backlog_mapped_sel.py`). `None` without a trace, without such a
family, or off the chip.
"""

import json


def read(ctx, match, exclude=(), also=()):
    ops = ctx["obs"].get("traced_ops")
    if not ops or not ops.get("decode_steps") \
            or ctx["device"]["platform"] != "tpu":
        return None
    wanted = [list(match)] + [list(m) for m in also]
    names = [n for n in ops["seconds"]
             if any(all(m in n for m in ms) for ms in wanted)
             and not any(x in n for x in exclude)]
    seconds = sum(ops["seconds"][n] for n in names)
    if not seconds:
        return None
    print(json.dumps({"op_ms": names,
                      "events": sum(ops["calls"][n] for n in names),
                      "seconds": seconds,
                      "decode_steps": ops["decode_steps"]}), flush=True)
    return 1000.0 * seconds / ops["decode_steps"]
