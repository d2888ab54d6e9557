"""Model step: share of the prefill programs' device time that the chip's
share of the expert layer takes: self time of the device ops of the router,
the dispatch, the held experts, the shared expert and the combine (how an
op is placed: lib/latent_trace.py) over the device time of the prefill
program, summed over the window's `prefill_chunk` steps.  %."""

from lib import latent_trace


def read(run):
    found = latent_trace.prefill_group_seconds(run)
    if found is None or not found[0]:
        return None
    program_s, by_group, _ = found
    return 100.0 * (by_group["experts"]
                    + by_group["expert_share_rest"]) / program_s
