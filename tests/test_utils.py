import os

import pytest

from distar_tpu.utils import (
    AverageMeter,
    Config,
    EMAMeter,
    VariableRecord,
    deep_merge_dicts,
    read_config,
    save_config,
)


def test_config_attribute_access():
    cfg = Config({"model": {"encoder": {"dim": 256}}, "lst": [{"a": 1}]})
    assert cfg.model.encoder.dim == 256
    assert cfg.lst[0].a == 1
    cfg.model.encoder.dim = 128
    assert cfg["model"]["encoder"]["dim"] == 128


def test_deep_merge_semantics():
    base = Config({"a": {"b": 1, "c": 2}, "d": [1, 2]})
    override = {"a": {"c": 3}, "d": [9]}
    merged = deep_merge_dicts(base, override)
    assert merged.a.b == 1 and merged.a.c == 3
    assert merged.d == [9]
    # base untouched
    assert base.a.c == 2


def test_yaml_roundtrip(tmp_path):
    cfg = Config({"learner": {"lr": 1e-4, "betas": [0.0, 0.99]}})
    p = os.path.join(tmp_path, "cfg.yaml")
    save_config(cfg, p)
    loaded = read_config(p)
    assert loaded.learner.lr == pytest.approx(1e-4)
    assert loaded.learner.betas == [0.0, 0.99]


def test_meters():
    m = AverageMeter(length=3)
    for v in [1, 2, 3, 4]:
        m.update(v)
    assert m.val == 4 and m.avg == pytest.approx(3.0)
    e = EMAMeter(alpha=0.5)
    e.update(0.0)
    e.update(1.0)
    # bias-corrected: weighted mean (alpha*0 + 1*1)/(alpha + 1), not the raw
    # EMA 0.5 (debias semantics, tests/test_obs_metrics.py)
    assert e.avg == pytest.approx(2.0 / 3.0)


def test_variable_record():
    rec = VariableRecord(length=10)
    rec.update_var({"loss": 1.0, "acc": 0.5})
    rec.update_var({"loss": 3.0})
    assert rec.get("loss").avg == pytest.approx(2.0)
    assert "loss" in rec.get_vars_text()


def test_downloader_resumes_with_range(tmp_path):
    """download_model resumes a partial file via HTTP Range (reference
    distar/bin/download_model.py:24-48)."""
    import http.server
    import threading

    payload = bytes(range(256)) * 40  # 10240 bytes

    class RangeHandler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            start = 0
            rng = self.headers.get("Range")
            if rng:
                start = int(rng.split("=")[1].rstrip("-"))
                self.send_response(206)
            else:
                self.send_response(200)
            body = payload[start:]
            self.send_header("Content-Length", str(len(payload) if not rng else len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), RangeHandler)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    try:
        from distar_tpu.bin.download_model import Downloader

        out = tmp_path / "model.pth"
        out.write_bytes(payload[:3000])  # partial file on disk
        url = f"http://127.0.0.1:{srv.server_address[1]}/model.pth"
        d = Downloader(url, str(out), timeout=5.0)
        assert d.total_size == len(payload)
        d.download()
        assert out.read_bytes() == payload
    finally:
        srv.shutdown()


def test_downloader_restarts_when_server_ignores_range(tmp_path):
    """A 200 response to a Range request must overwrite, not append."""
    import http.server
    import threading

    payload = b"x" * 5000

    class NoRangeHandler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            self.send_response(200)  # ignores Range entirely
            self.send_header("Content-Length", str(len(payload)))
            self.end_headers()
            self.wfile.write(payload)

        def log_message(self, *a):
            pass

    srv = http.server.HTTPServer(("127.0.0.1", 0), NoRangeHandler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        from distar_tpu.bin.download_model import Downloader

        out = tmp_path / "model.pth"
        out.write_bytes(b"y" * 1234)  # stale partial file
        d = Downloader(f"http://127.0.0.1:{srv.server_address[1]}/m", str(out))
        d.download()
        assert out.read_bytes() == payload
    finally:
        srv.shutdown()
