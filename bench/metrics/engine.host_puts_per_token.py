"""engine.host_puts_per_token: host-to-device inputs made for the
model steps per output token, from the program's counters at the end of
the run: ``serve.host_puts_total`` over ``serve.tokens_generated_total``
(a prefill puts its prompt, a decode step its token and position, so
a request of n tokens makes 2n - 1).  None where the program keeps no
such counter."""


def read(rec):
    from repro.obs import get_metrics

    reg = get_metrics()
    puts = reg.get("serve.host_puts_total")
    tokens = reg.get("serve.tokens_generated_total")
    if puts is None or tokens is None or not tokens.value:
        return None
    return puts.value / tokens.value
