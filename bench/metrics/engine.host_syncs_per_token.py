"""engine.host_syncs_per_token: device-to-host reads of the token loop
per output token, from the program's counters at the end of the run:
``serve.host_syncs_total`` over ``serve.tokens_generated_total`` (the
warm-up's tokens sync the same way).  None where the program keeps no
such counter."""


def read(rec):
    from repro.obs import get_metrics

    reg = get_metrics()
    syncs = reg.get("serve.host_syncs_total")
    tokens = reg.get("serve.tokens_generated_total")
    if syncs is None or tokens is None or not tokens.value:
        return None
    return syncs.value / tokens.value
