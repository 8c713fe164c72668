#!/usr/bin/env python3
"""Protoc replacement for toolchain-less containers (no protoc/cmake/ninja).

Parses proto/tpuft.proto (the simple proto3 subset this repo uses) and emits:
  - <gen_dir>/tpuft.pb.h            C++ classes speaking REAL protobuf wire
                                    format, no libprotobuf dependency
                                    (gen_dir: the one argument, default
                                    native/build-g++/gen in this checkout)
  - torchft_tpu/proto/tpuft_pb2.py  descriptor-pool-based real protobuf classes

With these, the native library rebuilds with nothing but g++:

    python native/gen_pb_local.py
    g++ -std=c++17 -O3 -fPIC -shared -I native/src -I native/build-g++/gen \
        native/src/wire.cc native/src/http.cc native/src/flight.cc \
        native/src/lighthouse.cc native/src/manager.cc native/src/store.cc \
        native/src/ring.cc native/src/capi.cc \
        -o torchft_tpu/_lib/libtpuft.so -lpthread

The authoritative source list is torchft_tpu._native.NATIVE_SOURCES — the
auto-build and tests/test_native_core.py's fallback both compile from it,
so a new .cc (e.g. ring.cc, the GIL-free ring engine) lands in every
recipe by editing one tuple.

Environments with the full toolchain should prefer cmake/ninja/protoc
(native/CMakeLists.txt) — protoc output and this stand-in are wire-compatible
(both are canonical proto3 encoding), so either side can build against a peer
built with the other.
"""
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PROTO = os.path.join(REPO, "proto", "tpuft.proto")

SCALARS = {
    "string": ("std::string", 9),
    "bytes": ("std::string", 12),
    "int64": ("int64_t", 3),
    "uint64": ("uint64_t", 4),
    "bool": ("bool", 8),
    # fixed64 wire type.  Scalar and repeated (packed fixed64, matching
    # protoc's proto3 default encoding) are generated; map double still is
    # not — add map-entry fixed64 support before using one.
    "double": ("double", 1),
}


def parse(path):
    msgs = []  # (name, [ (kind, type, name, number) ]) kind in scalar/msg/rep_scalar/rep_msg/map
    cur = None
    for raw in open(path):
        line = raw.split("//", 1)[0].strip()
        if not line:
            continue
        m = re.match(r"message\s+(\w+)\s*\{", line)
        if m:
            cur = (m.group(1), [])
            msgs.append(cur)
            if line.rstrip().endswith("}"):
                cur = None
            continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        m = re.match(r"map<\s*(\w+)\s*,\s*(\w+)\s*>\s+(\w+)\s*=\s*(\d+)\s*;", line)
        if m:
            cur[1].append(("map", (m.group(1), m.group(2)), m.group(3), int(m.group(4))))
            continue
        m = re.match(r"repeated\s+(\w+)\s+(\w+)\s*=\s*(\d+)\s*;", line)
        if m:
            t = m.group(1)
            kind = "rep_scalar" if t in SCALARS else "rep_msg"
            cur[1].append((kind, t, m.group(2), int(m.group(3))))
            continue
        m = re.match(r"(\w+)\s+(\w+)\s*=\s*(\d+)\s*;", line)
        if m:
            t = m.group(1)
            kind = "scalar" if t in SCALARS else "msg"
            cur[1].append((kind, t, m.group(2), int(m.group(3))))
            continue
    return msgs


def cpp_type(t):
    return SCALARS[t][0] if t in SCALARS else t


def gen_cpp(msgs):
    out = []
    w = out.append
    w("""// LOCAL COMPILE STAND-IN generated by native/gen_pb_local.py — real protobuf wire
// format, no libprotobuf dependency.  The driver image uses protoc output.
#pragma once
#include <cstdint>
#include <cstring>
#include <map>
#include <string>
#include <vector>

namespace tpuft {
namespace pbwire {
inline void PutVarint(std::string* o, uint64_t v) {
  while (v >= 0x80) { o->push_back(char((v & 0x7f) | 0x80)); v >>= 7; }
  o->push_back(char(v));
}
inline bool GetVarint(const std::string& in, size_t* pos, uint64_t* v) {
  uint64_t r = 0; int shift = 0;
  while (*pos < in.size() && shift < 64) {
    uint8_t b = uint8_t(in[*pos]); ++*pos;
    r |= uint64_t(b & 0x7f) << shift;
    if (!(b & 0x80)) { *v = r; return true; }
    shift += 7;
  }
  return false;
}
inline void PutTag(std::string* o, int f, int wt) { PutVarint(o, (uint64_t(f) << 3) | wt); }
inline void PutFixed64(std::string* o, uint64_t v) {
  for (int i = 0; i < 8; ++i) o->push_back(char((v >> (8 * i)) & 0xff));
}
inline bool GetFixed64(const std::string& in, size_t* pos, uint64_t* v) {
  if (*pos + 8 > in.size()) return false;
  uint64_t r = 0;
  for (int i = 0; i < 8; ++i) r |= uint64_t(uint8_t(in[*pos + size_t(i)])) << (8 * i);
  *pos += 8; *v = r; return true;
}
inline void PutLenField(std::string* o, int f, const std::string& s) {
  PutTag(o, f, 2); PutVarint(o, s.size()); o->append(s);
}
inline bool GetLen(const std::string& in, size_t* pos, std::string* s) {
  uint64_t n; if (!GetVarint(in, pos, &n)) return false;
  if (*pos + n > in.size()) return false;
  s->assign(in, *pos, size_t(n)); *pos += size_t(n); return true;
}
inline bool SkipField(const std::string& in, size_t* pos, int wt) {
  if (wt == 0) { uint64_t v; return GetVarint(in, pos, &v); }
  if (wt == 2) { std::string s; return GetLen(in, pos, &s); }
  if (wt == 5) { if (*pos + 4 > in.size()) return false; *pos += 4; return true; }
  if (wt == 1) { if (*pos + 8 > in.size()) return false; *pos += 8; return true; }
  return false;
}
}  // namespace pbwire
""")
    for name, fields in msgs:
        w(f"class {name} {{")
        w(" public:")
        # accessors
        for kind, t, fn, num in fields:
            if kind == "scalar":
                ct = cpp_type(t)
                if ct == "std::string":
                    w(f"  const std::string& {fn}() const {{ return {fn}_; }}")
                    w(f"  void set_{fn}(const std::string& v) {{ {fn}_ = v; }}")
                    w(f"  std::string* mutable_{fn}() {{ return &{fn}_; }}")
                else:
                    w(f"  {ct} {fn}() const {{ return {fn}_; }}")
                    w(f"  void set_{fn}({ct} v) {{ {fn}_ = v; }}")
            elif kind == "msg":
                w(f"  const {t}& {fn}() const {{ return {fn}_; }}")
                w(f"  {t}* mutable_{fn}() {{ return &{fn}_; }}")
            elif kind == "rep_msg":
                w(f"  const std::vector<{t}>& {fn}() const {{ return {fn}_; }}")
                w(f"  const {t}& {fn}(int i) const {{ return {fn}_[size_t(i)]; }}")
                w(f"  {t}* add_{fn}() {{ {fn}_.emplace_back(); return &{fn}_.back(); }}")
                w(f"  int {fn}_size() const {{ return int({fn}_.size()); }}")
            elif kind == "rep_scalar":
                ct = cpp_type(t)
                w(f"  const std::vector<{ct}>& {fn}() const {{ return {fn}_; }}")
                w(f"  const {ct}& {fn}(int i) const {{ return {fn}_[size_t(i)]; }}")
                w(f"  void add_{fn}(const {ct}& v) {{ {fn}_.push_back(v); }}")
                w(f"  int {fn}_size() const {{ return int({fn}_.size()); }}")
            elif kind == "map":
                kt, vt = cpp_type(t[0]), cpp_type(t[1])
                w(f"  const std::map<{kt}, {vt}>& {fn}() const {{ return {fn}_; }}")
                w(f"  std::map<{kt}, {vt}>* mutable_{fn}() {{ return &{fn}_; }}")
        # serialize
        w("  void SerializeToString(std::string* out) const {")
        w("    out->clear();")
        for kind, t, fn, num in fields:
            if kind == "scalar":
                ct = cpp_type(t)
                if ct == "std::string":
                    w(f"    if (!{fn}_.empty()) pbwire::PutLenField(out, {num}, {fn}_);")
                elif ct == "bool":
                    w(f"    if ({fn}_) {{ pbwire::PutTag(out, {num}, 0); pbwire::PutVarint(out, 1); }}")
                elif ct == "double":
                    w(f"    if ({fn}_ != 0) {{ pbwire::PutTag(out, {num}, 1); uint64_t bits; std::memcpy(&bits, &{fn}_, 8); pbwire::PutFixed64(out, bits); }}")
                else:
                    w(f"    if ({fn}_ != 0) {{ pbwire::PutTag(out, {num}, 0); pbwire::PutVarint(out, uint64_t({fn}_)); }}")
            elif kind == "msg":
                w(f"    {{ std::string sub; {fn}_.SerializeToString(&sub); pbwire::PutLenField(out, {num}, sub); }}")
            elif kind == "rep_msg":
                w(f"    for (const auto& m : {fn}_) {{ std::string sub; m.SerializeToString(&sub); pbwire::PutLenField(out, {num}, sub); }}")
            elif kind == "rep_scalar":
                ct = cpp_type(t)
                if ct == "std::string":
                    w(f"    for (const auto& s : {fn}_) pbwire::PutLenField(out, {num}, s);")
                elif ct == "double":
                    # Packed fixed64 — protoc's proto3 default for
                    # repeated double; wire-compatible both ways.
                    w(f"    if (!{fn}_.empty()) {{ std::string packed; for (auto v : {fn}_) {{ uint64_t bits; std::memcpy(&bits, &v, 8); pbwire::PutFixed64(&packed, bits); }} pbwire::PutLenField(out, {num}, packed); }}")
                else:
                    w(f"    if (!{fn}_.empty()) {{ std::string packed; for (auto v : {fn}_) pbwire::PutVarint(&packed, uint64_t(v)); pbwire::PutLenField(out, {num}, packed); }}")
            elif kind == "map":
                vt = cpp_type(t[1])
                if vt == "std::string":
                    w(f"    for (const auto& kv : {fn}_) {{ std::string e; pbwire::PutLenField(&e, 1, kv.first); if (!kv.second.empty()) pbwire::PutLenField(&e, 2, kv.second); pbwire::PutLenField(out, {num}, e); }}")
                else:
                    w(f"    for (const auto& kv : {fn}_) {{ std::string e; pbwire::PutLenField(&e, 1, kv.first); if (kv.second != 0) {{ pbwire::PutTag(&e, 2, 0); pbwire::PutVarint(&e, uint64_t(kv.second)); }} pbwire::PutLenField(out, {num}, e); }}")
        w("  }")
        # parse
        w("  bool ParseFromString(const std::string& in) {")
        w("    *this = {};")
        w("    size_t pos = 0;")
        w("    while (pos < in.size()) {")
        w("      uint64_t key; if (!pbwire::GetVarint(in, &pos, &key)) return false;")
        w("      int f = int(key >> 3); int wt = int(key & 7);")
        w("      switch (f) {")
        for kind, t, fn, num in fields:
            w(f"        case {num}: {{")
            if kind == "scalar":
                ct = cpp_type(t)
                if ct == "std::string":
                    w(f"          if (wt != 2) {{ if (!pbwire::SkipField(in, &pos, wt)) return false; break; }}")
                    w(f"          if (!pbwire::GetLen(in, &pos, &{fn}_)) return false;")
                elif ct == "double":
                    w(f"          if (wt != 1) {{ if (!pbwire::SkipField(in, &pos, wt)) return false; break; }}")
                    w(f"          uint64_t bits; if (!pbwire::GetFixed64(in, &pos, &bits)) return false; std::memcpy(&{fn}_, &bits, 8);")
                else:
                    w(f"          if (wt != 0) {{ if (!pbwire::SkipField(in, &pos, wt)) return false; break; }}")
                    w(f"          uint64_t v; if (!pbwire::GetVarint(in, &pos, &v)) return false; {fn}_ = {ct}(v);")
            elif kind == "msg":
                w(f"          if (wt != 2) {{ if (!pbwire::SkipField(in, &pos, wt)) return false; break; }}")
                w(f"          std::string sub; if (!pbwire::GetLen(in, &pos, &sub)) return false;")
                w(f"          if (!{fn}_.ParseFromString(sub)) return false;")
            elif kind == "rep_msg":
                w(f"          if (wt != 2) {{ if (!pbwire::SkipField(in, &pos, wt)) return false; break; }}")
                w(f"          std::string sub; if (!pbwire::GetLen(in, &pos, &sub)) return false;")
                w(f"          {fn}_.emplace_back(); if (!{fn}_.back().ParseFromString(sub)) return false;")
            elif kind == "rep_scalar":
                ct = cpp_type(t)
                if ct == "std::string":
                    w(f"          if (wt != 2) {{ if (!pbwire::SkipField(in, &pos, wt)) return false; break; }}")
                    w(f"          std::string s; if (!pbwire::GetLen(in, &pos, &s)) return false; {fn}_.push_back(s);")
                elif ct == "double":
                    w(f"          if (wt == 2) {{ std::string packed; if (!pbwire::GetLen(in, &pos, &packed)) return false;")
                    w(f"            size_t p2 = 0; while (p2 < packed.size()) {{ uint64_t bits; if (!pbwire::GetFixed64(packed, &p2, &bits)) return false; double v; std::memcpy(&v, &bits, 8); {fn}_.push_back(v); }}")
                    w(f"          }} else if (wt == 1) {{ uint64_t bits; if (!pbwire::GetFixed64(in, &pos, &bits)) return false; double v; std::memcpy(&v, &bits, 8); {fn}_.push_back(v); }}")
                    w(f"          else {{ if (!pbwire::SkipField(in, &pos, wt)) return false; }}")
                else:
                    w(f"          if (wt == 2) {{ std::string packed; if (!pbwire::GetLen(in, &pos, &packed)) return false;")
                    w(f"            size_t p2 = 0; while (p2 < packed.size()) {{ uint64_t v; if (!pbwire::GetVarint(packed, &p2, &v)) return false; {fn}_.push_back({ct}(v)); }}")
                    w(f"          }} else if (wt == 0) {{ uint64_t v; if (!pbwire::GetVarint(in, &pos, &v)) return false; {fn}_.push_back({ct}(v)); }}")
                    w(f"          else {{ if (!pbwire::SkipField(in, &pos, wt)) return false; }}")
            elif kind == "map":
                vt = cpp_type(t[1])
                w(f"          if (wt != 2) {{ if (!pbwire::SkipField(in, &pos, wt)) return false; break; }}")
                w(f"          std::string e; if (!pbwire::GetLen(in, &pos, &e)) return false;")
                if vt == "std::string":
                    w(f"          std::string k; std::string v; size_t p2 = 0;")
                else:
                    w(f"          std::string k; {vt} v = 0; size_t p2 = 0;")
                w(f"          while (p2 < e.size()) {{ uint64_t ek; if (!pbwire::GetVarint(e, &p2, &ek)) return false;")
                w(f"            int ef = int(ek >> 3); int ewt = int(ek & 7);")
                w(f"            if (ef == 1 && ewt == 2) {{ if (!pbwire::GetLen(e, &p2, &k)) return false; }}")
                if vt == "std::string":
                    w(f"            else if (ef == 2 && ewt == 2) {{ if (!pbwire::GetLen(e, &p2, &v)) return false; }}")
                else:
                    w(f"            else if (ef == 2 && ewt == 0) {{ uint64_t uv; if (!pbwire::GetVarint(e, &p2, &uv)) return false; v = {vt}(uv); }}")
                w(f"            else {{ if (!pbwire::SkipField(e, &p2, ewt)) return false; }} }}")
                w(f"          {fn}_[k] = v;")
            w("          break;")
            w("        }")
        w("        default: if (!pbwire::SkipField(in, &pos, wt)) return false; break;")
        w("      }")
        w("    }")
        w("    return true;")
        w("  }")
        # members
        w(" private:")
        for kind, t, fn, num in fields:
            if kind == "scalar":
                ct = cpp_type(t)
                init = "" if ct == "std::string" else (" = false" if ct == "bool" else " = 0")
                w(f"  {ct} {fn}_{init};")
            elif kind == "msg":
                w(f"  {t} {fn}_;")
            elif kind == "rep_msg":
                w(f"  std::vector<{t}> {fn}_;")
            elif kind == "rep_scalar":
                w(f"  std::vector<{cpp_type(t)}> {fn}_;")
            elif kind == "map":
                w(f"  std::map<{cpp_type(t[0])}, {cpp_type(t[1])}> {fn}_;")
        w("};")
        w("")
    w("}  // namespace tpuft")
    return "\n".join(out) + "\n"


PY_TYPE = {"string": 9, "bytes": 12, "int64": 3, "uint64": 4, "bool": 8, "double": 1}


def gen_py(msgs):
    lines = []
    w = lines.append
    w('"""Generated locally by native/gen_pb_local.py (descriptor-pool construction;')
    w('the driver image regenerates this module with real protoc)."""')
    w("from google.protobuf import descriptor_pb2, descriptor_pool, message_factory")
    w("")
    w("_fd = descriptor_pb2.FileDescriptorProto()")
    w("_fd.name = 'tpuft_local.proto'")
    w("_fd.package = 'tpuft'")
    w("_fd.syntax = 'proto3'")
    w("")
    for name, fields in msgs:
        mv = f"_m_{name}"
        w(f"{mv} = _fd.message_type.add()")
        w(f"{mv}.name = {name!r}")
        for kind, t, fn, num in fields:
            fv = f"_f_{name}_{fn}"
            w(f"{fv} = {mv}.field.add()")
            w(f"{fv}.name = {fn!r}")
            w(f"{fv}.number = {num}")
            if kind == "scalar":
                w(f"{fv}.label = 1")
                w(f"{fv}.type = {PY_TYPE[t]}")
            elif kind == "msg":
                w(f"{fv}.label = 1")
                w(f"{fv}.type = 11")
                w(f"{fv}.type_name = '.tpuft.{t}'")
            elif kind == "rep_msg":
                w(f"{fv}.label = 3")
                w(f"{fv}.type = 11")
                w(f"{fv}.type_name = '.tpuft.{t}'")
            elif kind == "rep_scalar":
                w(f"{fv}.label = 3")
                w(f"{fv}.type = {PY_TYPE[t]}")
            elif kind == "map":
                entry = "".join(p.capitalize() for p in fn.split("_")) + "Entry"
                ev = f"_e_{name}_{fn}"
                w(f"{ev} = {mv}.nested_type.add()")
                w(f"{ev}.name = {entry!r}")
                w(f"{ev}.options.map_entry = True")
                w(f"{ev}_k = {ev}.field.add()")
                w(f"{ev}_k.name = 'key'; {ev}_k.number = 1; {ev}_k.label = 1; {ev}_k.type = {PY_TYPE[t[0]]}")
                w(f"{ev}_v = {ev}.field.add()")
                w(f"{ev}_v.name = 'value'; {ev}_v.number = 2; {ev}_v.label = 1; {ev}_v.type = {PY_TYPE[t[1]]}")
                w(f"{fv}.label = 3")
                w(f"{fv}.type = 11")
                w(f"{fv}.type_name = '.tpuft.{name}.{entry}'")
        w("")
    w("_pool = descriptor_pool.Default()")
    w("try:")
    w("    _pool.Add(_fd)")
    w("except Exception:  # already registered (module re-imported)")
    w("    pass")
    w("")
    for name, _ in msgs:
        w(f"{name} = message_factory.GetMessageClass(_pool.FindMessageTypeByName('tpuft.{name}'))")
    return "\n".join(lines) + "\n"


def main():
    msgs = parse(PROTO)
    for name, fields in msgs:
        for kind, t, fn, _ in fields:
            if kind == "map" and "double" in t:
                raise NotImplementedError(
                    f"{name}.{fn}: map double needs fixed64 map-entry "
                    "support in gen_pb_local.py"
                )
    names = [n for n, _ in msgs]
    sys.stderr.write("messages: %s\n" % ", ".join(names))
    gen_dir = (
        sys.argv[1]
        if len(sys.argv) > 1
        else os.path.join(REPO, "native", "build-g++", "gen")
    )
    os.makedirs(gen_dir, exist_ok=True)
    header = os.path.join(gen_dir, "tpuft.pb.h")
    with open(header, "w") as f:
        f.write(gen_cpp(msgs))
    with open(os.path.join(REPO, "torchft_tpu", "proto", "tpuft_pb2.py"), "w") as f:
        f.write(gen_py(msgs))
    print(f"wrote {header} and torchft_tpu/proto/tpuft_pb2.py")


if __name__ == "__main__":
    main()
