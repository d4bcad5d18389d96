"""Share of the prefilled positions that were padding up to the bucket:
1 - real / padded of the program's ``lm_prefill_tokens_total`` over the
window."""


def read(window):
    real = window.counter_delta("lm_prefill_tokens_total", kind="real")
    padded = window.counter_delta("lm_prefill_tokens_total", kind="padded")
    if real is None or not padded:
        return None
    return 100.0 * (1.0 - real / padded)
