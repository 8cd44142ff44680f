"""Share of the router's choices that fell on a zero-compute (identity)
expert, in percent: 100 x zero / (zero + real), over the window's decode
calls and the double layers, from the device counters. About a third (256
of 768 outputs) under random weights; a trained router moves it with the
token."""


def read(obs):
    return obs.facts.get("zero_choice_pct")
