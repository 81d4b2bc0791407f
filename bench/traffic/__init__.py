"""Traffic: a mix is a data file ``bench/traffic/<mix>.json`` whose ``kind``
names the generator module ``bench/traffic/<kind>.py``. Every generator
exposes ``make(mix, seed, seconds, vocab) -> Traffic``."""
