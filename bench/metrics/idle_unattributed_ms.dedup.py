"""Window time per job in which no device ran an op and no program span
below ``er.run_er`` was open: the idle time the spans do not name."""
import spans


def seconds(sp, trace):
    return spans.idle_unattributed_s(sp, trace)


def read(rec):
    return spans.per_job_ms(rec, __file__, seconds)
