"""100 x (1 - the launch's phases added up over `launch_ms.total`, the
trainer's entry to the first step's drain): what of a start no phase accounts
for, as `unscoped_pct` is for the step. It keeps the phases honest after a
refactor."""


def read(run):
    ms = dict(run["summary"].get("launch_ms") or {})
    total = ms.pop("total", None)
    if not total:
        return None
    return 100.0 * (1.0 - sum(ms.values()) / total)
