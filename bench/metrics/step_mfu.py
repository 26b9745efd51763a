"""Model FLOPs of the window's work over what the chips' bf16 peak gives in
the devices' busy time, in percent: the busy time is the average over the
chips used, so the peak is one chip's times their number.

The work is what reached the client inside the traced window: every decode
token after a request's first, at its own context, and the prefill of every
request whose first token arrived in it.  The prefix cache's tokens are
left out in proportion: a prompt counts ``1 - hit_share`` of its tokens as
computed, the last ones.  FLOPs are ``bench/flops.py``'s."""
import flops
from record import hit_share


def read(rec):
    tr = rec["trace"]
    if not tr or tr["busy_s"] <= 0:
        return None
    m, T = rec["m"], rec["seconds"]
    cached = hit_share(rec) or 0.0
    work = 0.0
    for r in rec["requests"]:
        ts, p = r["times"], r["prompt_len"]
        if ts and ts[0] < T:
            work += flops.prefill(m, p, cached * p)
        for j, t in enumerate(ts[1:], start=1):
            if t < T:
                work += flops.decode_token(m, p + j)
    peak = rec["chips"] * rec["peaks"]["bf16_flops_per_s"]
    return 100.0 * work / (tr["busy_s"] * peak)
