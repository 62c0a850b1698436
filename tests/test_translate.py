"""Transpilers (≙ the fork's translate/ subsystem; the reference tests
these by compiling packages containing .h/.schema.json/.md resources)."""

import ctypes
import ctypes.util
import os
import sys

import _child
from ponyc_tpu.translate import (translate_c_header, translate_dir,
                                 translate_json_schema,
                                 translate_text_resource)

HDR = """
// demo header
#define MAX_THINGS 32
#define SCALE 2.5
enum Mode { MODE_OFF, MODE_ON = 5, MODE_AUTO };
typedef unsigned int u32;

int add_numbers(int a, int b);
double scale_value(double v);
size_t buf_len(const char *s);
void reset(void);
u32 mask_bits(u32 x, unsigned shift);
int printf(const char *fmt, ...);   // variadic → skipped
"""


def _load_generated(src: str, name: str, tmp_path):
    path = tmp_path / (name + ".py")
    path.write_text(src)
    sys.path.insert(0, str(tmp_path))
    try:
        import importlib
        mod = importlib.import_module(name)
        importlib.reload(mod)
        return mod
    finally:
        sys.path.pop(0)


def test_c_header_bindings_run_against_real_lib(tmp_path):
    src = translate_c_header(HDR, name="demo.h")
    mod = _load_generated(src, "demo_ffi", tmp_path)
    # constants from #define and enum
    assert mod.MAX_THINGS == 32
    assert mod.SCALE == 2.5
    assert mod.MODE_OFF == 0 and mod.MODE_ON == 5 and mod.MODE_AUTO == 6
    # variadic printf was skipped, not bound
    assert not hasattr(mod, "printf")
    # Compile the implementation and call through the bindings.
    c = tmp_path / "demo.c"
    c.write_text("""
#include <stddef.h>
#include <string.h>
int add_numbers(int a, int b) { return a + b; }
double scale_value(double v) { return v * 2.5; }
size_t buf_len(const char *s) { return strlen(s); }
void reset(void) {}
unsigned mask_bits(unsigned x, unsigned s) { return x >> s; }
""")
    so = tmp_path / "libdemo.so"
    r = _child.run(["gcc", "-shared", "-fPIC", "-o", str(so), str(c)],
                   timeout=60)
    assert r.returncode == 0, r.stderr
    mod.bind(str(so))
    assert mod.add_numbers(2, 40) == 42
    assert abs(mod.scale_value(2.0) - 5.0) < 1e-9
    assert mod.buf_len(b"hello") == 5
    assert mod.mask_bits(0xF0, 4) == 0x0F
    mod.reset()


SCHEMA = """
{
  "title": "job",
  "description": "A queued job.",
  "type": "object",
  "required": ["id"],
  "properties": {
    "id": {"type": "integer"},
    "name": {"type": "string"},
    "weight": {"type": "number"},
    "urgent": {"type": "boolean"},
    "tags": {"type": "array", "items": {"type": "string"}},
    "owner": {
      "type": "object",
      "title": "owner",
      "properties": {
        "uid": {"type": "integer"},
        "email": {"type": "string"}
      }
    }
  }
}
"""


def test_json_schema_roundtrip(tmp_path):
    src = translate_json_schema(SCHEMA, name="job.schema.json")
    mod = _load_generated(src, "job_schema", tmp_path)
    j = mod.Job.from_json(
        '{"id": 7, "name": "x", "weight": 1.5, "urgent": true,'
        ' "tags": ["a","b"], "owner": {"uid": 3, "email": "e@x"}}')
    assert j.id == 7 and j.urgent is True and j.tags == ["a", "b"]
    assert j.owner.uid == 3
    back = mod.Job.from_json(j.to_json())
    assert back.to_dict() == j.to_dict()
    # defaults for non-required fields
    k = mod.Job.from_json('{"id": 1}')
    assert k.name == "" and k.weight == 0.0 and k.tags == []
    # device-actor field specs derived from flat scalars
    assert mod.Job.ACTOR_FIELDS == {"id": "I32", "weight": "F32",
                                    "urgent": "Bool"}


def test_text_resource_and_dir_dispatch(tmp_path):
    src_dir = tmp_path / "resources"
    out_dir = tmp_path / "generated"
    src_dir.mkdir()
    (src_dir / "notes.md").write_text("# Title\nBody ≥ stuff\n")
    (src_dir / "config.json").write_text('{"a": 1}')
    (src_dir / "job.schema.json").write_text(SCHEMA)
    (src_dir / "demo.h").write_text(HDR)
    (src_dir / "ignored.bin").write_text("xx")
    paths = translate_dir(str(src_dir), str(out_dir))
    names = sorted(os.path.basename(p) for p in paths)
    assert names == ["config.py", "demo.py", "job.py", "notes.py"]
    sys.path.insert(0, str(tmp_path))
    try:
        from generated import config, notes  # noqa
        assert notes.TEXT.startswith("# Title")
        assert config.DATA == {"a": 1}
    finally:
        sys.path.pop(0)


def test_text_resource_unicode():
    out = translate_text_resource("héllo ≙ wörld", name="x.txt")
    ns = {}
    exec(out, ns)
    assert ns["TEXT"] == "héllo ≙ wörld"
