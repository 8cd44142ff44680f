"""Programs that reached the backend while a training loop of the program
ran: registry counter ``compile.in_loop``, raised by the compile listener
between ``LoopSpans``' first ``returned()`` and the loop's end, where it
happens (the JSONL's ``compile:in_loop`` names program and step). 0 in a
healthy cell, as the benchmark's own ``compiles_in_window``. ``None`` where
no step ran through ``LoopSpans`` (the LM cells' windows, whose loop is the
benchmark's copy) or the program has no such counter."""


def read(obs):
    from tpu_sandbox.obs import get_registry

    counters = get_registry().snapshot()["counters"]
    if not counters.get("train.steps"):
        return None
    return counters.get("compile.in_loop")
