"""Rows the served expert share dropped, a decode call, all layers: held
assignments beyond the buffer's R rows, from the device counters. Must read
0: a dropped row is a token whose expert's output is missing, and the
reference drops none."""


def read(obs):
    return obs.facts.get("serve_moe_rows_dropped")
