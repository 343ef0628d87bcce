"""Extend-wave padding (%): the share of the positions the extend waves
computed that carried no token.  Every extend wave computes
``max_slots x K`` positions whatever the slots catching up; the
registry's ``engine.extend_positions`` counts them and
``engine.extend_fed`` the ones fed a real token, both read before the
window and after the drain.  Moves ``itl_p95_ms``: the ITL tail is an
extend wave, whose length is set by the positions it computes."""


def read(rec):
    before, after = rec["registry"]
    pos, fed = "engine.extend_positions", "engine.extend_fed"
    if pos not in after or fed not in after:
        return None
    n = after[pos] - before.get(pos, 0)
    if not n:
        return None
    return 100.0 * (1.0 - (after[fed] - before.get(fed, 0)) / n)
