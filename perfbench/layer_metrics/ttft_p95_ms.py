"""95th percentile, over all requests started in the window, of the
client's time from sending ``POST /generate`` to the first token line (a
failed request counts as infinite).  In a closed loop that keeps every
slot busy this tail swings by a tenth from run to run, so it stands here
and not among the end-to-end metrics (PERF.md, section 2)."""

import harness


def read(window):
    ttft = window.stats.get("ttft_s")
    return 1e3 * harness.percentile(ttft, 95) if ttft else None
