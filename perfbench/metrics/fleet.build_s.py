"""Host time per fleet call building the node traces: make_workload,
build_slot_trace and the padding."""


def read(rec):
    return rec.get("build_s") if rec["kind"] == "fleet" else None
