"""Host time of decoding stage-1 survivors per job: the
``er.stage1.decode`` spans, one per stage-1 chunk."""
import spans


def seconds(sp, trace):
    return spans.seconds(sp, "er.stage1.decode")


def read(rec):
    return spans.per_job_ms(rec, __file__, seconds)
