"""Programs that reached the backend (compiled, or loaded from the cache)
while the train state was made and placed: the count of the program's
histograms ``compile.backend_s{under=...}`` under the spans
``setup:model_init``, ``setup:opt_init`` and ``place:state``. An eager,
op-by-op init reads in the hundreds, a jitted one a handful (``ROADMAP.md``
A8(a)). ``None`` where the program keeps no such record."""

UNDER = ("setup:model_init", "setup:opt_init", "place:state")


def phases_under(spans=UNDER) -> dict[str, dict] | None:
    """phase -> ``{"count", "sum"}`` over the series under ``spans``, or
    None where no compile histogram exists at all."""
    from tpu_sandbox.obs import get_registry

    hists = get_registry().snapshot()["histograms"]
    if not any(key.startswith("compile.backend_s{") for key in hists):
        return None
    out = {}
    for phase in ("trace", "lower", "backend"):
        series = [hists.get(f"compile.{phase}_s{{under={span}}}")
                  for span in spans]
        out[phase] = {"count": sum(h["count"] for h in series if h),
                      "sum": sum(h["sum"] for h in series if h)}
    return out


def read(obs):
    phases = phases_under()
    return None if phases is None else phases["backend"]["count"]
