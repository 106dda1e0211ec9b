"""Slots whose recurrent state a decode tick advanced: the program's
``state_slots_live`` counter (a pass's live lanes, summed over the
passes read), a mean a tick. A dead slot's state is carried through the
tick untouched; the fuller the ticks, the more tokens each read of the
weights serves. Moves serve_tokens_per_s."""


def read(run):
    c = run["counters"]
    if not c.get("decode_ticks") or not c.get("state_slots_live"):
        return None
    return c["state_slots_live"] / c["decode_ticks"]
