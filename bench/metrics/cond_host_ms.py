"""Host milliseconds a batch spends stacking its lanes' conditioning and
placing it on the device (the engine's ``serving.build_cond`` span,
``DiffusionResult.cond_host_s``), the mean over the batches completed in
the window.  Nothing to read where the results carry no such time or
no batch carried conditioning."""


def read(run):
    per_batch = {}
    for a in run.in_window():
        secs = getattr(a.result, "cond_host_s", None)
        if secs is None:
            return None
        per_batch[a.result.batch] = secs
    if not any(per_batch.values()):
        return None
    return 1e3 * sum(per_batch.values()) / len(per_batch)
