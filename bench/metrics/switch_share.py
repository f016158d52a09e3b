"""Share (%) of the window's time inside ``serve`` not spent in the model's
steps (the harness's own callback time left out): the switch, planning and
copies. ``switch_share.open`` and ``switch_share.closed`` are this reader in
the open-loop and the closed-loop cells."""


def read(rec):
    return rec.switch_share()
