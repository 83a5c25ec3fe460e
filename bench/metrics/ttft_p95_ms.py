"""95th percentile of time to first token (admission to first token,
``t_first - t_admit`` of the program's records) over the requests whose
first token came in the traced window.  In the closed loop the prefill
queue sets it: one chunk a tick, so a slot waits behind every slot ahead
of it in prefill."""
from bench.lib.traffic import percentile


def read(run):
    ttft = run.out.get("ttft_s")
    if not ttft:
        return None
    return percentile(ttft, 95) * 1e3
