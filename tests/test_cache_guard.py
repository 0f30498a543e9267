"""ops/cache_guard: the vm.max_map_count raise and its opt-out."""

import builtins

from lighthouse_tpu.ops import cache_guard


def test_env_opt_out_never_touches_the_sysctl(monkeypatch):
    monkeypatch.setenv("LHTPU_NO_CACHE_GUARD", "1")

    def boom(*a, **k):  # pragma: no cover - must not be reached
        raise AssertionError("sysctl opened despite opt-out")

    monkeypatch.setattr(builtins, "open", boom)
    assert cache_guard.ensure_map_headroom() is False


def test_unreadable_sysctl_reports_false(monkeypatch, tmp_path):
    monkeypatch.delenv("LHTPU_NO_CACHE_GUARD", raising=False)
    monkeypatch.setattr(cache_guard, "_MAP_PATH", str(tmp_path / "absent"))
    assert cache_guard.ensure_map_headroom() is False


def test_ceiling_already_at_target_is_not_rewritten(monkeypatch, tmp_path):
    monkeypatch.delenv("LHTPU_NO_CACHE_GUARD", raising=False)
    path = tmp_path / "max_map_count"
    path.write_text(str(cache_guard._MAP_TARGET))
    path.chmod(0o444)
    monkeypatch.setattr(cache_guard, "_MAP_PATH", str(path))
    assert cache_guard.ensure_map_headroom() is True
    assert path.read_text() == str(cache_guard._MAP_TARGET)
