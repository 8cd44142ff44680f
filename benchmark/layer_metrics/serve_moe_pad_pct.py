"""Share of the served expert share's row buffer that held no row, in
percent: 100 x (1 - rows held / R), mean over the double layers and the
window's decode calls, from the counters the programs carry on the device
beside the pages (read once before and once after the window). What a
static buffer costs a decode step; ``None`` where the program counts no
rows."""


def read(obs):
    return obs.facts.get("serve_moe_pad_pct")
