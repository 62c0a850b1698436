"""C header EXPORT (translate/c_header_export.py) — the outbound half
of FFI (≙ genheader.c:256): the emitted header must compile under g++
and agree with the program's actual ids and layouts."""

import tempfile
import os

import _child
from ponyc_tpu import (F32, I32, Iso, Ref, Runtime, RuntimeOptions,  # noqa
                       VecF32, actor, behaviour)
from ponyc_tpu.translate import export_header, write_header


@actor
class Sensor:
    hub: Ref["Hub"]
    reading: F32

    @behaviour
    def sample(self, st, v: F32, seq: I32):
        self.send(st["hub"], Hub.collect, v, when=seq >= 0)
        return {**st, "reading": v}

    @behaviour
    def rewire(self, st, h: Ref["Hub"]):
        return {**st, "hub": h}


@actor
class Hub:
    total: F32
    MAX_SENDS = 0

    @behaviour
    def collect(self, st, v: F32):
        return {**st, "total": st["total"] + v}

    @behaviour
    def calibrate(self, st, coeffs: VecF32[3], blob: Iso):
        return st


def _build():
    opts = RuntimeOptions(mailbox_cap=8, batch=1, max_sends=1,
                          msg_words=4, inject_slots=8)
    rt = Runtime(opts)
    rt.declare(Sensor, 4).declare(Hub, 2).start()
    return rt, opts


def test_header_reflects_program_abi():
    rt, opts = _build()
    text = export_header(rt.program, opts)
    gid = {b.actor_type.__name__ + "." + b.name: b.global_id
           for b in rt.program.behaviour_table}
    assert f"PONYC_TPU_GID_SENSOR_SAMPLE = {gid['Sensor.sample']}" in text
    assert f"PONYC_TPU_GID_HUB_COLLECT = {gid['Hub.collect']}" in text
    assert "#define PONYC_TPU_MSG_WORDS 4" in text
    assert "#define PONYC_TPU_HUB_MSG_WORDS 4" in text      # Vec3 + Iso
    assert "#define PONYC_TPU_SENSOR_MSG_WORDS 2" in text   # F32 + I32
    assert "float coeffs[3];" in text
    assert "Iso host-heap handle" in text
    assert "Ref[Hub] actor id" in text


def test_header_compiles_under_gpp():
    rt, opts = _build()
    with tempfile.TemporaryDirectory() as d:
        h = write_header(rt.program, opts, os.path.join(d, "prog.h"))
        main = os.path.join(d, "main.cc")
        gid = {b.actor_type.__name__ + "." + b.name: b.global_id
               for b in rt.program.behaviour_table}
        with open(main, "w") as f:
            f.write(f'''
#include "prog.h"
#include <cstdio>
int main() {{
  struct ponyc_tpu_Sensor_sample_args a;
  a.v = 1.5f; a.seq = 7;
  struct ponyc_tpu_msg m;
  m.behaviour_id = PONYC_TPU_GID_SENSOR_SAMPLE;
  static_assert(PONYC_TPU_GID_SENSOR_SAMPLE == {gid['Sensor.sample']},
                "gid");
  static_assert(PONYC_TPU_SENSOR_SAMPLE_ARG_WORDS == 2, "width");
  static_assert(PONYC_TPU_HUB_CALIBRATE_ARG_WORDS == 4, "vec+iso");
  std::printf("%d %d\\n", m.behaviour_id, a.seq);
  return 0;
}}
''')
        exe = os.path.join(d, "a.out")
        r = _child.run(["g++", "-std=c++17", "-Wall", "-Werror",
                        main, "-o", exe], timeout=60)
        assert r.returncode == 0, r.stderr
        out = _child.run([exe], timeout=10)
        assert out.stdout.split() == [str(gid["Sensor.sample"]), "7"]


def test_narrow_ints_occupy_full_words():
    """Every one-word spec is a full int32 wire word (pack.spec_width
    widens narrow ints) — the struct layout must agree so memcpy into
    ponyc_tpu_msg.words is mechanical (round-5 review regression)."""
    from ponyc_tpu import I16, U8

    @actor
    class Narrowed:
        x: I32

        @behaviour
        def put(self, st, a: I16, b: U8):
            return st

    opts = RuntimeOptions(mailbox_cap=8, batch=1, max_sends=1,
                          msg_words=2, inject_slots=8)
    rt = Runtime(opts)
    rt.declare(Narrowed, 1).start()
    text = export_header(rt.program, opts)
    assert "int32_t /* i16 value range */ a;" in text
    assert "int32_t /* u8 value range */ b;" in text
    assert "int16_t" not in text and "int8_t" not in text
    assert "#define PONYC_TPU_NARROWED_PUT_ARG_WORDS 2" in text
