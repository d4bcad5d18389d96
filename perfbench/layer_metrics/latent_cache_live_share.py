"""The share of the latent slot arena a decode step has a use for: the
mean ``context_tokens`` of the window's ``lm.step`` spans over ``slots x
max_len``.  A step that reads the whole arena reads the rest for nothing.
Read only where the program says its arena holds latent rows
(``lm_cache_bytes{kind="latent"}``)."""


def read(window):
    if not any(m["name"] == "lm_cache_bytes"
               and m["labels"].get("kind") == "latent" and m.get("value")
               for m in window.counters1["metrics"]):
        return None
    steps = [e["args"]["context_tokens"] for e in window.spans
             if e["name"] == "lm.step"
             and "context_tokens" in e.get("args", {})]
    server = window.cell.traffic.get("server", {})
    rows = server.get("slots", 0) * server.get("max_len", 0)
    if not steps or not rows:
        return None
    return 100.0 * (sum(steps) / len(steps)) / rows
