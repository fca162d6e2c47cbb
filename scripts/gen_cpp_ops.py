#!/usr/bin/env python
"""Generate cpp-package/include/mxtpu/ops.hpp from the live op table.

Parity with the reference's generated C++ op-wrapper API
(cpp-package/include/mxnet-cpp/op.h, ~10k generated LoC from nnvm op
metadata via OpWrapperGenerator.py): every public callable in mx.np /
mx.npx gets typed inline C++ functions (1/2/3-array overloads + a
JSON-kwargs tail) that route through MXTPUImperativeInvoke.

Run:  python scripts/gen_cpp_ops.py            (rewrites ops.hpp)
      python scripts/gen_cpp_ops.py --check    (exit 1 when ops.hpp
                                                drifted from the table)
"""
from __future__ import annotations

import argparse
import keyword
import os
import sys

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
OUT = os.path.join(ROOT, "cpp-package", "include", "mxtpu", "ops.hpp")

CPP_KEYWORDS = {
    "and", "or", "not", "xor", "delete", "new", "union", "register",
    "float", "double", "int", "long", "short", "char", "bool", "void",
    "this", "template", "typename", "class", "struct", "enum",
    "default", "operator", "export", "try", "catch", "throw",
    "switch", "case", "break", "continue", "return", "if", "else",
    "for", "while", "do", "static", "const", "auto", "signed",
    "unsigned", "true", "false", "nullptr", "inline", "extern",
}


def cpp_name(name):
    if name in CPP_KEYWORDS or keyword.iskeyword(name):
        return name + "_op"
    return name


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--check", action="store_true",
                    help="compare with ops.hpp, write nothing; exit 1 "
                         "when it drifted from the op table")
    args = ap.parse_args()     # any other argument is an error
    sys.path.insert(0, ROOT)
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=1")
    import jax
    jax.config.update("jax_platforms", "cpu")
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    import mxnet_tpu as mx
    from opperf import enumerate_ops

    ops = enumerate_ops(mx)
    # top level: plain np names and npx:-prefixed keys; linalg/random/
    # fft become nested C++ namespaces with their own prefixed keys
    entries = []            # (cpp function name, op-table key)
    sub = {"linalg": [], "random": [], "fft": []}
    seen = set()
    for qual in sorted(ops):
        prefix, name = qual.split(".", 1)
        if prefix == "np":
            key, fn = name, cpp_name(name)
        elif prefix == "npx":
            key, fn = f"npx:{name}", cpp_name(name)
        else:
            sub[prefix].append((cpp_name(name), f"{prefix}:{name}"))
            continue
        if fn in seen:
            continue  # np name wins over npx duplicate
        seen.add(fn)
        entries.append((fn, key))

    lines = [
        "// GENERATED by scripts/gen_cpp_ops.py — do not edit by hand.",
        "// Typed C++ wrappers for every mx.np / mx.npx operator",
        "// (parity: the reference's generated cpp-package op.h).",
        "// Arity is enforced at runtime by the op itself; kwargs ride",
        "// as a JSON object string, e.g. ops::topk(x, \"{\\\"k\\\": 3}\").",
        "#ifndef MXTPU_OPS_HPP_",
        "#define MXTPU_OPS_HPP_",
        "",
        "#include <mxtpu/ndarray.hpp>",
        "",
        "#include <string>",
        "",
        "namespace mxtpu {",
        "namespace ops {",
        "",
    ]
    for fn, key in entries:
        lines += [
            f"inline NDArray {fn}(const NDArray& a,",
            f"                    const std::string& kw = \"\") {{",
            f"  return detail::Invoke(\"{key}\", {{a.handle()}}, kw);",
            "}",
            f"inline NDArray {fn}(const NDArray& a, const NDArray& b,",
            f"                    const std::string& kw = \"\") {{",
            f"  return detail::Invoke(\"{key}\", {{a.handle(), "
            "b.handle()}, kw);",
            "}",
            f"inline NDArray {fn}(const NDArray& a, const NDArray& b,",
            f"                    const NDArray& c,",
            f"                    const std::string& kw = \"\") {{",
            f"  return detail::Invoke(\"{key}\", {{a.handle(), "
            "b.handle(), c.handle()}, kw);",
            "}",
            "",
        ]
    n_sub = 0
    for ns in ("linalg", "random", "fft"):
        lines += [f"namespace {ns} {{", ""]
        for fn, key in sub[ns]:
            n_sub += 1
            lines += [
                f"inline NDArray {fn}(const NDArray& a,",
                f"                    const std::string& kw = \"\") {{",
                f"  return detail::Invoke(\"{key}\", {{a.handle()}}, "
                "kw);",
                "}",
                f"inline NDArray {fn}(const NDArray& a, "
                "const NDArray& b,",
                f"                    const std::string& kw = \"\") {{",
                f"  return detail::Invoke(\"{key}\", {{a.handle(), "
                "b.handle()}, kw);",
                "}",
                "",
            ]
        lines += [f"}}  // namespace {ns}", ""]
    lines += [
        "}  // namespace ops",
        "}  // namespace mxtpu",
        "",
        "#endif  // MXTPU_OPS_HPP_",
        "",
    ]
    text = "\n".join(lines)
    if args.check:
        on_disk = open(OUT).read() if os.path.exists(OUT) else ""
        if on_disk != text:
            print("ops.hpp is STALE — rerun scripts/gen_cpp_ops.py")
            return 1
        print(f"ops.hpp current ({len(entries)} ops + {n_sub} "
              "namespaced)")
        return 0
    with open(OUT, "w") as f:
        f.write(text)
    print(f"wrote {OUT}: {len(entries)} ops x 3 overloads + "
          f"{n_sub} namespaced, {len(lines)} lines")
    return 0


if __name__ == "__main__":
    sys.exit(main())
