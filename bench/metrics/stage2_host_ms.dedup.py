"""Host side of stage 2 per job: the ``er.stage2`` span less its
``er.stage2.sync`` children (the edit-similarity call and the wait for
its result), so the chunks' gathers, padding and selection."""
import spans


def seconds(sp, trace):
    return spans.seconds_outside(sp, "er.stage2", "er.stage2.sync")


def read(rec):
    return spans.per_job_ms(rec, __file__, seconds)
