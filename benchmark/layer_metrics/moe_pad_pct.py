"""Share of the experts' row buffer that held no row, in percent:
100 x (1 - rows held / R), mean over the expert layers and the window's
steps, from the counters the step accumulates on the device (read once
after the window). What a static buffer costs a share under random
weights; ``None`` where the program counts no rows."""


def read(obs):
    return obs.facts.get("moe_pad_pct")
