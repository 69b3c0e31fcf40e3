"""Host time of featurizing per job: the ``er.featurize`` span
(``er/encode``: trigram codes and hashed features)."""
import spans


def seconds(sp, trace):
    return spans.seconds(sp, "er.featurize")


def read(rec):
    return spans.per_job_ms(rec, __file__, seconds)
