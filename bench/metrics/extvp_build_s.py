"""Load job (core/extvp_build.py, kernels/semijoin.py): seconds of the
ExtVP build, `storage_report()["extvp_build_seconds"]`; nothing to read
where the configuration builds no ExtVP."""


def read(run):
    if not run.config.get("with_extvp"):
        return None
    return run.storage.get("extvp_build_seconds")
