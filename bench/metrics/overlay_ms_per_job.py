"""overlay_ms_per_job: a job's wall time outside its executor call
(client, forwarder, gateway, scheduler), mean over the window's jobs."""


def read(record, trace):
    spans = record.get("overlay_s")
    return 1e3 * sum(spans) / len(spans) if spans else None
