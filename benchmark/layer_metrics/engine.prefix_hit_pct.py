"""Engine: share of prompt tokens served from the prefix cache, over the
`admit` events of the window.  %."""

from lib import runview


def read(run):
    admits = runview.window_events(run, "admit")
    total = sum(e["prompt_len"] for e in admits)
    if not total:
        return None
    return 100.0 * sum(e["cached"] for e in admits) / total
