"""Host preprocessing: seconds the trainer's construction took (partition,
expand, pad, budgets), on the host clock, inside set-up."""


def read(rec):
    return rec["counters"].get("host_prep_s")
