"""Layer equations live in ``bench/models/<workload>.py``: DeepSeek-V3's
give the numbers the harness computed before they moved there, and a
configuration with equations of its own needs new files alone."""
import json
import shutil

import pytest

from _cell import run
from harness.manifest import BENCH_DIR, ROOT, Manifest, ManifestError
from harness.reference import Request

CONFIGS = ["dsv3-hbm4-x8", "dsv3-rome-x9"]
DECODE = "decode-poisson"


def doc():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def equations(config: str, traffic: str):
    cell = Manifest.load().cell(f"{config}.{traffic}")
    return cell.equations, cell.config, cell.traffic


@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("traffic,weights", [(DECODE, 231_104),
                                             ("fleet8-bursty", 946_601_984)])
def test_deepseek_v3_weights_and_pools(config, traffic, weights):
    eq, cfg, tr = equations(config, traffic)
    assert eq.weight_bytes(cfg, tr) == weights
    assert eq.pool_geometry(cfg) == {"n_kv_heads": 1, "head_dim": 288,
                                     "rows_per_page": 9}


# 2 pools, 576 B per token per pool, 64 tokens per page: whole pages of
# the context read from both pools, one token appended to both
@pytest.mark.parametrize("config", CONFIGS)
@pytest.mark.parametrize("context,read", [(1, 73_728), (63, 73_728),
                                          (64, 73_728), (65, 147_456),
                                          (4096, 4_718_592)])
def test_deepseek_v3_request_bytes(config, context, read):
    eq, cfg, tr = equations(config, DECODE)
    for rid, seed in [(0, 0), (7, 2 ** 31 + 5)]:
        assert eq.request_bytes(cfg, tr, Request(rid, context, seed)) == (
            read, 1152)


def bench_with(tmp_path, module_text: str | None, workload="deepseek-v3"):
    """A bench directory holding a copied configuration ``dsv3-copy``
    (of ``dsv3-rome-x9``, its ``workload`` set to ``workload``) and, when
    ``module_text`` is given, ``models/<workload>.py`` with that text;
    the manifest declaring its decode cell."""
    bench = tmp_path / "bench"
    shutil.copytree(BENCH_DIR, bench, ignore=shutil.ignore_patterns(
        "tests", "models", ".traces", "__pycache__"))
    (bench / "models").mkdir()
    if module_text is not None:
        (bench / "models" / f"{workload}.py").write_text(module_text)
    cfg = json.loads((bench / "configs" / "dsv3-rome-x9.json").read_text())
    cfg.update(name="dsv3-copy", workload=workload)
    (bench / "configs" / "dsv3-copy.json").write_text(json.dumps(cfg))
    d = doc()
    d["configs"].append(dict(d["configs"][1], name="dsv3-copy",
                             file="bench/configs/dsv3-copy.json"))
    d["workloads"].append({"name": "dsv3-copy.decode-poisson",
                           "config": "dsv3-copy", "traffic": DECODE,
                           "chips": 1, "why": "test"})
    return Manifest(d, bench)


DSV3 = (BENCH_DIR / "models" / "deepseek-v3.py").read_text()
ONE_PAGE_MORE = DSV3 + '''

_request_bytes = request_bytes


def request_bytes(config, traffic, req):
    read, write = _request_bytes(config, traffic, req)
    kv = config["kv"]
    return read + kv["rows_per_page"] * kv["row_bytes"], write
'''


@pytest.mark.parametrize("text,bad", [(DSV3, False), (ONE_PAGE_MORE, True)])
def test_copied_configuration_is_checked_by_its_own_module(tmp_path, text,
                                                           bad):
    out = run("dsv3-copy.decode-poisson",
              manifest=bench_with(tmp_path, text))
    assert out["checked_steps"] > 0
    bytes_bad = out["checks"]["bytes_bad"]["value"]
    if bad:
        assert bytes_bad > 0 and not out["correct"]
    else:
        assert bytes_bad == 0 and out["correct"], out["checks"]


@pytest.mark.parametrize("case", ["missing", "no_request_bytes"])
def test_module_missing_or_incomplete_refused_at_load(tmp_path, case):
    text = (None if case == "missing"
            else DSV3.replace("def request_bytes(", "def _unused("))
    m = bench_with(tmp_path, text, workload="new-model")
    with pytest.raises(ManifestError, match="models/new-model.py"):
        m.cell("dsv3-copy.decode-poisson")
