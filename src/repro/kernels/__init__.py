"""Pallas TPU kernels, each with a pure-jnp oracle (``ref.py``) and a
public wrapper (``ops.py``). Every call names ``interpret``: ``True``
runs the Pallas interpreter (CPU tests), ``False`` compiles with Mosaic
(TPU).

``REAL_WIDTHS`` are the call shapes at published model widths that
``chip_smoke.py`` runs on the chip and ``tests/test_tpu_compile.py``
compiles for a described v5e chip.
"""

REAL_WIDTHS = {
    # h2o-danube-1.8b decode: 32 query / 8 KV heads of 80, the serve
    # smoke run's 2 slots x 128 cached positions.
    "flash_decode": dict(b=2, h=32, hkv=8, s=128, d=80),
    # h2o-danube-1.8b FFN projection, one 8-row decode tile.
    "rowstream_matmul": dict(m=8, k=2560, n=6912),
    # rwkv6-3b time mix: 40 heads of 64, a few hundred tokens.
    "rwkv_scan": dict(b=1, s=384, H=40, hd=64),
}
