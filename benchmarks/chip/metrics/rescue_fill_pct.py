"""Share of the mate-rescue sweeps' padded rows that hold a candidate
window: rows swept over the pow-2 bucket they were padded to, summed over
the ``repro/pairing/rescue`` spans of the traced window."""
import progtrace


def read(run):
    prog = progtrace.for_run(run)
    if prog is None:
        return None
    st = prog["stats"].get("repro/pairing/rescue", {})
    if not st.get("bucket"):
        return None
    return 100.0 * st["rows"] / st["bucket"]
