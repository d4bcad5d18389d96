"""The whole serving step's share of the chip's peak: model FLOPs of the
real prompt tokens prefilled and the tokens decoded in the traced part
of the window (padding not counted; the decode step's attention over the
cache not counted either: its context is not in the spans, and it is
under a twentieth of a token's FLOPs at these lengths) over the traced
seconds times the peak bf16 FLOP/s.  Both come from the program's spans
(``lm.step``, ``lm.prefill`` and their arguments) that began in the traced
part and that part's length on the host's clock; nothing is read from the
trace, which only says which part of the window it is."""

import flops


def read(window):
    if window.tables is None or window.traced is None:
        return None
    cfg = window.cell.config
    count = flops.of_family(cfg["family"])
    steps = window.spans_in_trace("lm.step")
    prefills = window.spans_in_trace("lm.prefill")
    if not steps and not prefills:
        return None
    decoded = sum(e.get("args", {}).get("active", 0) for e in steps)
    total = decoded * count.decode_flops(cfg, 1)
    total += sum(count.prefill_flops(cfg, e["args"]["prompt_tokens"])
                 for e in prefills)
    seconds = window.traced[1] - window.traced[0]
    peak = flops.peaks(window.device_kind)["bf16_flops_per_s"]
    return 100.0 * total / (seconds * window.stats["chips"] * peak)
