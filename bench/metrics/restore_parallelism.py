"""Restore work per second of restore: the blob reads and the decoding
(``fetch_s`` + ``decode_s`` of the restore's ``Incarnation.timings``,
each summed over the leaf threads and their chunk tasks) over
``materialize_s``. A program without the restore's chunk pool
(no ``decode_pool_chunks``) leaves it out."""

KEYS = ("fetch_s", "decode_s", "materialize_s", "decode_pool_chunks")


def read(run, trace):
    r = run.get("restore") or {}
    if not all(k in r for k in KEYS) or r["materialize_s"] <= 0:
        return None
    return (r["fetch_s"] + r["decode_s"]) / r["materialize_s"]
