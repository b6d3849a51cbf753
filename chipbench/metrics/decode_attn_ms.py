"""Device time under the ``attention`` scope of the decode step program,
per run: the union of its ops' intervals."""


def read(run):
    return run.layer("decode_attn_ms")
