"""Share of the device's busy time spent in the cascade's exit heads:
device self time of ops whose HLO ``op_name`` sits innermost in an
``exit_head<k>`` scope (``busy_by_scope`` of ``trace_split``) over busy
time, in percent.  Nothing where the program sets no scope."""
import trace_split


def read(r):
    split = trace_split.for_readings(r)
    scopes = (split or {}).get("busy_by_scope") or {}
    if not split or not split["busy_s"] or set(scopes) <= {"other"}:
        return None
    heads = sum(v for k, v in scopes.items() if k.startswith("exit_head"))
    return 100.0 * heads / split["busy_s"]
