"""Host milliseconds per 1,000 sets verified in the window that the
program's dispatch spends on its own work: the stages in which the host
packs, draws scalars, schedules the MSM, hashes to the curve (dedup and
the map's enqueue) and enqueues the programs. The stages in which the
host waits on the device (``device_sync``, ``htc_cofactor``) and the
``hash_to_curve`` span that holds them are left out. From the program's
bls_dispatch_stage_seconds span histogram."""

HOST_STAGES = ("pack", "scalars", "msm_schedule", "dispatch", "htc_dedup",
               "htc_map")


def read(facts):
    stages = facts.get("stage_seconds") or {}
    if not facts.get("sets") or not any(s in stages for s in HOST_STAGES):
        return None
    return sum(stages.get(s, 0.0) for s in HOST_STAGES) * 1e6 / facts["sets"]
