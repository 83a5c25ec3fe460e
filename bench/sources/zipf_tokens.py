"""Token rows for a language-model training mix: ids with Zipf-distributed
frequencies over the configuration's vocabulary (``bench.lib.traffic``).

Mix keys: ``exponent`` (the Zipf exponent), ``examples`` (rows the
source pretends to hold)."""
from bench.lib.traffic import ZipfTokens


def make(data: dict, seed: int, conf: dict):
    return ZipfTokens(seed, conf["vocab_size"], data["exponent"],
                      data["examples"])
