"""The small checkout of ``bench/tests/tiny.py`` at a CPU size for the
latent-attention MoE serving kind too: a few short requests of two rows."""

from bench.tests import tiny

tiny.SMALL_TRAFFIC.setdefault(
    "serve_queue_mla_moe",
    dict(requests=3, batch=2, prompt=3, gen=8, cache_len=16, check_rows=2))
