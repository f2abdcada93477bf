"""Percent of the writes' critical path charged to the messenger stage
by the stage fold of every finished write's span tree (spans recorded
at sample rate 1 in the traced run)."""


def read(run):
    st = run.facts.get("stages") or {}
    total = st.get("total", 0.0)
    return 100.0 * st["messenger"] / total if total > 0 else None
