"""Seconds in the program's construction: ``build()`` / ``TrainState.create`` /
``TransformerLM.init``, host clock."""


def read(obs):
    return obs.facts.get("init_s")
