"""Decoding of the restore outside its blob reads (zlib, XOR, sparse
patch): ``decode_s`` of the restore's ``Incarnation.timings``, the
``ckpt.restore.decode`` spans' seconds less their fetches, summed over
the decode workers (so over threads, not wall time)."""


def read(run, trace):
    return (run.get("restore") or {}).get("decode_s")
