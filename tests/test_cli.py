"""CLI command tests (reference: ctl/*_test.go).

Most CLI surface is covered end-to-end elsewhere (import/export/backup in
test_http.py / test_backup.py; server boot in test_clusterproc.py). Here:
the introspection commands that only print.
"""

import io
from contextlib import redirect_stdout

import pytest
import tomllib

from pilosa_tpu.cli import main


def _run(argv):
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = main(argv)
    return rc, buf.getvalue()


def test_generate_config_is_valid_toml():
    rc, out = _run(["generate-config"])
    assert rc == 0
    cfg = tomllib.loads(out)
    assert cfg["bind"] == "127.0.0.1:10101"


def test_config_prints_effective_merge(tmp_path, monkeypatch):
    """`config` prints the file < env < flags merge the server would run
    with (reference: cmd/root.go:71-78 + ctl/config.go Run marshals the
    viper-merged server.Config)."""
    p = tmp_path / "c.toml"
    p.write_text('bind = "10.0.0.1:7777"\nmax-op-n = 5\n'
                 '[[cluster.nodes]]\nhost = "n1:10101"\n')
    monkeypatch.setenv("PILOSA_TPU_DATA_DIR", "/env/dir")
    rc, out = _run(["config", "--config", str(p), "--replicas", "3"])
    assert rc == 0
    cfg = tomllib.loads(out)
    assert cfg["bind"] == "10.0.0.1:7777"          # file
    assert cfg["data-dir"] == "/env/dir"           # env beats default
    assert cfg["replicas"] == 3                    # flag
    assert cfg["max-op-n"] == 5
    assert cfg["cluster"]["nodes"] == [{"host": "n1:10101"}]
    from pilosa_tpu.shardwidth import EXPONENT

    assert cfg["shard-width-exponent"] == EXPONENT


def test_config_flag_beats_file(tmp_path):
    p = tmp_path / "c.toml"
    p.write_text('bind = "10.0.0.1:7777"\n')
    rc, out = _run(["config", "--config", str(p),
                    "--bind", "0.0.0.0:1234"])
    assert rc == 0
    assert tomllib.loads(out)["bind"] == "0.0.0.0:1234"


def test_holder_command(tmp_path, monkeypatch):
    """`holder` opens the data dir, loads, prints a summary, shuts down
    (reference: cmd/server.go:33-57 newHolderCmd diagnostic)."""
    from pilosa_tpu.core import FieldOptions, Holder

    d = str(tmp_path / "hd")
    h = Holder(d).open()
    idx = h.create_index("diag")
    idx.create_field("f")
    idx.create_field("v", FieldOptions.int_field(min=0, max=10))
    idx.field("f").set_bit(1, 2)
    h.close()

    monkeypatch.delenv("PILOSA_TPU_DATA_DIR", raising=False)
    rc, out = _run(["holder", "--data-dir", d])
    assert rc == 0
    assert "indexes: 1" in out
    assert "diag: " in out and "f(set)" in out and "v(int)" in out

    # a mistyped path must error, not be silently created and blessed
    rc, _out = _run(["holder", "--data-dir", str(tmp_path / "typo")])
    assert rc == 1


@pytest.mark.parametrize("flag", ["--coalesce-window=2ms",
                                  "--coalesce-max-queue=8"])
@pytest.mark.parametrize("command", ["server", "config"])
def test_the_coalescers_flags_are_refused(command, flag, capsys):
    """One batcher (GroupCommit, no knob): both parsers refuse the
    deleted coalescer's options before anything starts, and neither
    help text lists them."""
    with pytest.raises(SystemExit) as exit_:
        main([command, flag])
    assert exit_.value.code == 2
    assert "unrecognized arguments: " + flag in capsys.readouterr().err
    with pytest.raises(SystemExit) as exit_:
        main([command, "--help"])
    assert exit_.value.code == 0
    assert "coalesce" not in capsys.readouterr().out
