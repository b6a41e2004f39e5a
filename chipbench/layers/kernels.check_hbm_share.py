"""Share of the chip's published HBM bandwidth that the window's checks
account for while the device is busy: completed checks x the
configuration's frozen ``hbm_bytes_per_check`` / (device busy seconds x
peak bytes/s).  The constant is the work a check is, fixed when the
configuration was added; it is not the live layout's byte model."""


def read(before, after, trace, cell):
    per_check = cell["config"].get("hbm_bytes_per_check")
    busy_s = trace.get("busy_s")
    checks = cell["window"]["checks"]
    if not (per_check and busy_s and checks):
        return None
    return 100.0 * checks * per_check / (busy_s * cell["peak"]["hbm_bytes_per_s"])
