"""Rows of held experts dropped because the share's total passed R, mean a
step over the window (summed over the expert layers), from the device
counters. ``None`` where the program counts no rows."""


def read(obs):
    return obs.facts.get("moe_rows_dropped")
