"""Unit tests for pure helpers.

Mirrors the reference's only unit test, TestConvertBytes
(cmd/root_test.go:10-32), and extends coverage to naming and Go-duration
parsing.
"""

import pytest

from klogs_tpu.ui import term
from klogs_tpu.utils import (
    FILE_NAME_SEPARATOR,
    convert_bytes,
    default_log_path,
    log_file_name,
    parse_duration,
    split_log_file_name,
)
from klogs_tpu.utils.duration import DurationError


class TestConvertBytes:
    # Table mirrors cmd/root_test.go:13-26 (incl. flooring: 1.5 KB -> "1 KB")
    @pytest.mark.parametrize(
        "n,expected",
        [
            (1, "1 B"),
            (1023, "1023 B"),
            (1024, "1 KB"),
            (1536, "1 KB"),  # 1.5 KB floors to 1 KB
            (1024 * 1024 - 1, "1023 KB"),
            (1024 * 1024, "1 MB"),
            (10 * 1024 * 1024 + 512 * 1024, "10 MB"),
            # the reference never renders GB (cmd/root.go:433)
            (5 * 1024 * 1024 * 1024, "5120 MB"),
        ],
    )
    def test_plain(self, n, expected):
        assert convert_bytes(n) == expected

    def test_zero_is_red(self):
        # cmd/root_test.go:17 expects the pterm-colored zero
        term.set_colors(True)
        assert convert_bytes(0) == "\x1b[31m0 B\x1b[0m"
        term.set_colors(False)
        assert convert_bytes(0) == "0 B"


class TestNaming:
    def test_separator(self):
        assert FILE_NAME_SEPARATOR == "__"

    def test_file_name(self):
        assert log_file_name("web-1", "nginx") == "web-1__nginx.log"

    def test_round_trip(self):
        name = log_file_name("api-abc", "sidecar")
        assert split_log_file_name("/tmp/x/" + name) == ("api-abc", "sidecar")

    def test_split_rejects_foreign_files(self):
        with pytest.raises(ValueError):
            split_log_file_name("notes.txt")

    def test_default_path_format(self):
        # logs/<YYYY-MM-DDTHH-MM> at minute granularity (cmd/root.go:47)
        import re

        assert re.fullmatch(
            r"logs/\d{4}-\d{2}-\d{2}T\d{2}-\d{2}", default_log_path().replace("\\", "/")
        )


class TestParseDuration:
    @pytest.mark.parametrize(
        "text,seconds",
        [
            ("5s", 5.0),
            ("2m", 120.0),
            ("3h", 10800.0),
            ("1.5h", 5400.0),
            ("2h45m", 9900.0),
            ("300ms", 0.3),
            ("100us", 1e-4),
            ("0", 0.0),
            ("-1.5h", -5400.0),
        ],
    )
    def test_valid(self, text, seconds):
        assert parse_duration(text) == pytest.approx(seconds)

    @pytest.mark.parametrize("text", ["", "5", "h", "5x", "1d", "s5", "-", "+", " 5s "])
    def test_invalid(self, text):
        with pytest.raises(DurationError):
            parse_duration(text)


@pytest.mark.parametrize("env,want", [
    ({"JAX_COMPILATION_CACHE_DIR": "/elsewhere", "JAX_PLATFORMS": "tpu"},
     None),  # JAX reads the variable itself; nothing is set in code
    ({"JAX_PLATFORMS": "cpu"}, None),  # a test or rehearsal
    ({"JAX_PLATFORMS": "tpu"}, "checkout"),
    ({}, "checkout"),
])
def test_compile_cache_dir_placement(monkeypatch, env, want):
    """JAX's compile cache goes where JAX_COMPILATION_CACHE_DIR says, or
    else to one fixed directory in the checkout — never a temp, pid or
    time-stamped path, which no later process would find again."""
    import os

    from klogs_tpu.utils import cache

    for k in ("JAX_COMPILATION_CACHE_DIR", "JAX_PLATFORMS"):
        monkeypatch.delenv(k, raising=False)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    got = cache.compile_cache_dir()
    if want is None:
        assert got is None
        return
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert got == os.path.join(repo, ".jax_cache") == cache.JAX_CACHE_DIR
