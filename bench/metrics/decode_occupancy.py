"""Share of the slot table the decode steps ran: 100 x slots decoded,
summed over the decode steps, / (decode steps x slots), in percent. None
where the program does not count them."""


def read(name, rec):
    steps, slots = rec.get("decode_steps"), rec.get("slots")
    if not steps or not slots or "decode_slot_steps" not in rec:
        return None
    return 100.0 * rec["decode_slot_steps"] / (steps * slots)
