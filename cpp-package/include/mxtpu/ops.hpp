// GENERATED by scripts/gen_cpp_ops.py — do not edit by hand.
// Typed C++ wrappers for every mx.np / mx.npx operator
// (parity: the reference's generated cpp-package op.h).
// Arity is enforced at runtime by the op itself; kwargs ride
// as a JSON object string, e.g. ops::topk(x, "{\"k\": 3}").
#ifndef MXTPU_OPS_HPP_
#define MXTPU_OPS_HPP_

#include <mxtpu/ndarray.hpp>

#include <string>

namespace mxtpu {
namespace ops {

inline NDArray abs(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("abs", {a.handle()}, kw);
}
inline NDArray abs(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("abs", {a.handle(), b.handle()}, kw);
}
inline NDArray abs(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("abs", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray absolute(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("absolute", {a.handle()}, kw);
}
inline NDArray absolute(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("absolute", {a.handle(), b.handle()}, kw);
}
inline NDArray absolute(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("absolute", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray add(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("add", {a.handle()}, kw);
}
inline NDArray add(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("add", {a.handle(), b.handle()}, kw);
}
inline NDArray add(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("add", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray all(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("all", {a.handle()}, kw);
}
inline NDArray all(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("all", {a.handle(), b.handle()}, kw);
}
inline NDArray all(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("all", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray allclose(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("allclose", {a.handle()}, kw);
}
inline NDArray allclose(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("allclose", {a.handle(), b.handle()}, kw);
}
inline NDArray allclose(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("allclose", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray alltrue(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("alltrue", {a.handle()}, kw);
}
inline NDArray alltrue(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("alltrue", {a.handle(), b.handle()}, kw);
}
inline NDArray alltrue(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("alltrue", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray amax(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("amax", {a.handle()}, kw);
}
inline NDArray amax(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("amax", {a.handle(), b.handle()}, kw);
}
inline NDArray amax(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("amax", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray amin(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("amin", {a.handle()}, kw);
}
inline NDArray amin(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("amin", {a.handle(), b.handle()}, kw);
}
inline NDArray amin(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("amin", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray angle(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("angle", {a.handle()}, kw);
}
inline NDArray angle(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("angle", {a.handle(), b.handle()}, kw);
}
inline NDArray angle(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("angle", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray any(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("any", {a.handle()}, kw);
}
inline NDArray any(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("any", {a.handle(), b.handle()}, kw);
}
inline NDArray any(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("any", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray append(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("append", {a.handle()}, kw);
}
inline NDArray append(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("append", {a.handle(), b.handle()}, kw);
}
inline NDArray append(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("append", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray apply_op(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("apply_op", {a.handle()}, kw);
}
inline NDArray apply_op(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("apply_op", {a.handle(), b.handle()}, kw);
}
inline NDArray apply_op(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("apply_op", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray arange(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("arange", {a.handle()}, kw);
}
inline NDArray arange(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("arange", {a.handle(), b.handle()}, kw);
}
inline NDArray arange(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("arange", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray arccos(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("arccos", {a.handle()}, kw);
}
inline NDArray arccos(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("arccos", {a.handle(), b.handle()}, kw);
}
inline NDArray arccos(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("arccos", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray arccosh(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("arccosh", {a.handle()}, kw);
}
inline NDArray arccosh(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("arccosh", {a.handle(), b.handle()}, kw);
}
inline NDArray arccosh(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("arccosh", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray arcsin(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("arcsin", {a.handle()}, kw);
}
inline NDArray arcsin(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("arcsin", {a.handle(), b.handle()}, kw);
}
inline NDArray arcsin(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("arcsin", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray arcsinh(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("arcsinh", {a.handle()}, kw);
}
inline NDArray arcsinh(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("arcsinh", {a.handle(), b.handle()}, kw);
}
inline NDArray arcsinh(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("arcsinh", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray arctan(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("arctan", {a.handle()}, kw);
}
inline NDArray arctan(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("arctan", {a.handle(), b.handle()}, kw);
}
inline NDArray arctan(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("arctan", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray arctan2(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("arctan2", {a.handle()}, kw);
}
inline NDArray arctan2(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("arctan2", {a.handle(), b.handle()}, kw);
}
inline NDArray arctan2(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("arctan2", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray arctanh(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("arctanh", {a.handle()}, kw);
}
inline NDArray arctanh(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("arctanh", {a.handle(), b.handle()}, kw);
}
inline NDArray arctanh(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("arctanh", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray argmax(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("argmax", {a.handle()}, kw);
}
inline NDArray argmax(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("argmax", {a.handle(), b.handle()}, kw);
}
inline NDArray argmax(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("argmax", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray argmin(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("argmin", {a.handle()}, kw);
}
inline NDArray argmin(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("argmin", {a.handle(), b.handle()}, kw);
}
inline NDArray argmin(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("argmin", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray argpartition(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("argpartition", {a.handle()}, kw);
}
inline NDArray argpartition(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("argpartition", {a.handle(), b.handle()}, kw);
}
inline NDArray argpartition(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("argpartition", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray argsort(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("argsort", {a.handle()}, kw);
}
inline NDArray argsort(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("argsort", {a.handle(), b.handle()}, kw);
}
inline NDArray argsort(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("argsort", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray argwhere(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("argwhere", {a.handle()}, kw);
}
inline NDArray argwhere(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("argwhere", {a.handle(), b.handle()}, kw);
}
inline NDArray argwhere(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("argwhere", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray around(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("around", {a.handle()}, kw);
}
inline NDArray around(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("around", {a.handle(), b.handle()}, kw);
}
inline NDArray around(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("around", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray array(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("array", {a.handle()}, kw);
}
inline NDArray array(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("array", {a.handle(), b.handle()}, kw);
}
inline NDArray array(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("array", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray array_equal(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("array_equal", {a.handle()}, kw);
}
inline NDArray array_equal(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("array_equal", {a.handle(), b.handle()}, kw);
}
inline NDArray array_equal(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("array_equal", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray array_equiv(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("array_equiv", {a.handle()}, kw);
}
inline NDArray array_equiv(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("array_equiv", {a.handle(), b.handle()}, kw);
}
inline NDArray array_equiv(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("array_equiv", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray array_split(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("array_split", {a.handle()}, kw);
}
inline NDArray array_split(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("array_split", {a.handle(), b.handle()}, kw);
}
inline NDArray array_split(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("array_split", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray asarray(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("asarray", {a.handle()}, kw);
}
inline NDArray asarray(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("asarray", {a.handle(), b.handle()}, kw);
}
inline NDArray asarray(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("asarray", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray ascontiguousarray(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("ascontiguousarray", {a.handle()}, kw);
}
inline NDArray ascontiguousarray(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("ascontiguousarray", {a.handle(), b.handle()}, kw);
}
inline NDArray ascontiguousarray(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("ascontiguousarray", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray atleast_1d(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("atleast_1d", {a.handle()}, kw);
}
inline NDArray atleast_1d(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("atleast_1d", {a.handle(), b.handle()}, kw);
}
inline NDArray atleast_1d(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("atleast_1d", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray atleast_2d(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("atleast_2d", {a.handle()}, kw);
}
inline NDArray atleast_2d(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("atleast_2d", {a.handle(), b.handle()}, kw);
}
inline NDArray atleast_2d(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("atleast_2d", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray atleast_3d(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("atleast_3d", {a.handle()}, kw);
}
inline NDArray atleast_3d(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("atleast_3d", {a.handle(), b.handle()}, kw);
}
inline NDArray atleast_3d(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("atleast_3d", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray average(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("average", {a.handle()}, kw);
}
inline NDArray average(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("average", {a.handle(), b.handle()}, kw);
}
inline NDArray average(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("average", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray bincount(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("bincount", {a.handle()}, kw);
}
inline NDArray bincount(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("bincount", {a.handle(), b.handle()}, kw);
}
inline NDArray bincount(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("bincount", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray bitwise_and(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("bitwise_and", {a.handle()}, kw);
}
inline NDArray bitwise_and(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("bitwise_and", {a.handle(), b.handle()}, kw);
}
inline NDArray bitwise_and(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("bitwise_and", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray bitwise_not(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("bitwise_not", {a.handle()}, kw);
}
inline NDArray bitwise_not(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("bitwise_not", {a.handle(), b.handle()}, kw);
}
inline NDArray bitwise_not(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("bitwise_not", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray bitwise_or(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("bitwise_or", {a.handle()}, kw);
}
inline NDArray bitwise_or(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("bitwise_or", {a.handle(), b.handle()}, kw);
}
inline NDArray bitwise_or(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("bitwise_or", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray bitwise_xor(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("bitwise_xor", {a.handle()}, kw);
}
inline NDArray bitwise_xor(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("bitwise_xor", {a.handle(), b.handle()}, kw);
}
inline NDArray bitwise_xor(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("bitwise_xor", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray blackman(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("blackman", {a.handle()}, kw);
}
inline NDArray blackman(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("blackman", {a.handle(), b.handle()}, kw);
}
inline NDArray blackman(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("blackman", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray broadcast_arrays(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("broadcast_arrays", {a.handle()}, kw);
}
inline NDArray broadcast_arrays(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("broadcast_arrays", {a.handle(), b.handle()}, kw);
}
inline NDArray broadcast_arrays(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("broadcast_arrays", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray broadcast_to(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("broadcast_to", {a.handle()}, kw);
}
inline NDArray broadcast_to(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("broadcast_to", {a.handle(), b.handle()}, kw);
}
inline NDArray broadcast_to(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("broadcast_to", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray can_cast(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("can_cast", {a.handle()}, kw);
}
inline NDArray can_cast(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("can_cast", {a.handle(), b.handle()}, kw);
}
inline NDArray can_cast(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("can_cast", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray cbrt(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("cbrt", {a.handle()}, kw);
}
inline NDArray cbrt(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("cbrt", {a.handle(), b.handle()}, kw);
}
inline NDArray cbrt(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("cbrt", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray ceil(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("ceil", {a.handle()}, kw);
}
inline NDArray ceil(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("ceil", {a.handle(), b.handle()}, kw);
}
inline NDArray ceil(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("ceil", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray clip(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("clip", {a.handle()}, kw);
}
inline NDArray clip(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("clip", {a.handle(), b.handle()}, kw);
}
inline NDArray clip(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("clip", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray column_stack(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("column_stack", {a.handle()}, kw);
}
inline NDArray column_stack(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("column_stack", {a.handle(), b.handle()}, kw);
}
inline NDArray column_stack(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("column_stack", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray compress(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("compress", {a.handle()}, kw);
}
inline NDArray compress(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("compress", {a.handle(), b.handle()}, kw);
}
inline NDArray compress(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("compress", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray concat(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("concat", {a.handle()}, kw);
}
inline NDArray concat(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("concat", {a.handle(), b.handle()}, kw);
}
inline NDArray concat(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("concat", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray concatenate(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("concatenate", {a.handle()}, kw);
}
inline NDArray concatenate(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("concatenate", {a.handle(), b.handle()}, kw);
}
inline NDArray concatenate(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("concatenate", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray conj(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("conj", {a.handle()}, kw);
}
inline NDArray conj(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("conj", {a.handle(), b.handle()}, kw);
}
inline NDArray conj(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("conj", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray conjugate(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("conjugate", {a.handle()}, kw);
}
inline NDArray conjugate(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("conjugate", {a.handle(), b.handle()}, kw);
}
inline NDArray conjugate(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("conjugate", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray convolve(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("convolve", {a.handle()}, kw);
}
inline NDArray convolve(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("convolve", {a.handle(), b.handle()}, kw);
}
inline NDArray convolve(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("convolve", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray copy(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("copy", {a.handle()}, kw);
}
inline NDArray copy(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("copy", {a.handle(), b.handle()}, kw);
}
inline NDArray copy(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("copy", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray copysign(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("copysign", {a.handle()}, kw);
}
inline NDArray copysign(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("copysign", {a.handle(), b.handle()}, kw);
}
inline NDArray copysign(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("copysign", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray corrcoef(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("corrcoef", {a.handle()}, kw);
}
inline NDArray corrcoef(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("corrcoef", {a.handle(), b.handle()}, kw);
}
inline NDArray corrcoef(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("corrcoef", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray correlate(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("correlate", {a.handle()}, kw);
}
inline NDArray correlate(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("correlate", {a.handle(), b.handle()}, kw);
}
inline NDArray correlate(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("correlate", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray cos(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("cos", {a.handle()}, kw);
}
inline NDArray cos(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("cos", {a.handle(), b.handle()}, kw);
}
inline NDArray cos(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("cos", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray cosh(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("cosh", {a.handle()}, kw);
}
inline NDArray cosh(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("cosh", {a.handle(), b.handle()}, kw);
}
inline NDArray cosh(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("cosh", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray count_nonzero(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("count_nonzero", {a.handle()}, kw);
}
inline NDArray count_nonzero(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("count_nonzero", {a.handle(), b.handle()}, kw);
}
inline NDArray count_nonzero(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("count_nonzero", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray cov(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("cov", {a.handle()}, kw);
}
inline NDArray cov(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("cov", {a.handle(), b.handle()}, kw);
}
inline NDArray cov(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("cov", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray cross(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("cross", {a.handle()}, kw);
}
inline NDArray cross(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("cross", {a.handle(), b.handle()}, kw);
}
inline NDArray cross(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("cross", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray cumprod(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("cumprod", {a.handle()}, kw);
}
inline NDArray cumprod(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("cumprod", {a.handle(), b.handle()}, kw);
}
inline NDArray cumprod(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("cumprod", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray cumsum(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("cumsum", {a.handle()}, kw);
}
inline NDArray cumsum(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("cumsum", {a.handle(), b.handle()}, kw);
}
inline NDArray cumsum(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("cumsum", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray current_context(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("current_context", {a.handle()}, kw);
}
inline NDArray current_context(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("current_context", {a.handle(), b.handle()}, kw);
}
inline NDArray current_context(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("current_context", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray deg2rad(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("deg2rad", {a.handle()}, kw);
}
inline NDArray deg2rad(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("deg2rad", {a.handle(), b.handle()}, kw);
}
inline NDArray deg2rad(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("deg2rad", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray degrees(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("degrees", {a.handle()}, kw);
}
inline NDArray degrees(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("degrees", {a.handle(), b.handle()}, kw);
}
inline NDArray degrees(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("degrees", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray delete_op(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("delete", {a.handle()}, kw);
}
inline NDArray delete_op(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("delete", {a.handle(), b.handle()}, kw);
}
inline NDArray delete_op(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("delete", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray diag(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("diag", {a.handle()}, kw);
}
inline NDArray diag(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("diag", {a.handle(), b.handle()}, kw);
}
inline NDArray diag(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("diag", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray diag_indices_from(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("diag_indices_from", {a.handle()}, kw);
}
inline NDArray diag_indices_from(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("diag_indices_from", {a.handle(), b.handle()}, kw);
}
inline NDArray diag_indices_from(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("diag_indices_from", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray diagflat(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("diagflat", {a.handle()}, kw);
}
inline NDArray diagflat(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("diagflat", {a.handle(), b.handle()}, kw);
}
inline NDArray diagflat(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("diagflat", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray diagonal(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("diagonal", {a.handle()}, kw);
}
inline NDArray diagonal(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("diagonal", {a.handle(), b.handle()}, kw);
}
inline NDArray diagonal(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("diagonal", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray diff(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("diff", {a.handle()}, kw);
}
inline NDArray diff(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("diff", {a.handle(), b.handle()}, kw);
}
inline NDArray diff(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("diff", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray divide(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("divide", {a.handle()}, kw);
}
inline NDArray divide(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("divide", {a.handle(), b.handle()}, kw);
}
inline NDArray divide(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("divide", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray dot(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("dot", {a.handle()}, kw);
}
inline NDArray dot(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("dot", {a.handle(), b.handle()}, kw);
}
inline NDArray dot(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("dot", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray dsplit(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("dsplit", {a.handle()}, kw);
}
inline NDArray dsplit(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("dsplit", {a.handle(), b.handle()}, kw);
}
inline NDArray dsplit(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("dsplit", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray dstack(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("dstack", {a.handle()}, kw);
}
inline NDArray dstack(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("dstack", {a.handle(), b.handle()}, kw);
}
inline NDArray dstack(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("dstack", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray ediff1d(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("ediff1d", {a.handle()}, kw);
}
inline NDArray ediff1d(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("ediff1d", {a.handle(), b.handle()}, kw);
}
inline NDArray ediff1d(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("ediff1d", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray einsum(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("einsum", {a.handle()}, kw);
}
inline NDArray einsum(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("einsum", {a.handle(), b.handle()}, kw);
}
inline NDArray einsum(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("einsum", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray empty(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("empty", {a.handle()}, kw);
}
inline NDArray empty(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("empty", {a.handle(), b.handle()}, kw);
}
inline NDArray empty(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("empty", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray empty_like(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("empty_like", {a.handle()}, kw);
}
inline NDArray empty_like(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("empty_like", {a.handle(), b.handle()}, kw);
}
inline NDArray empty_like(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("empty_like", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray equal(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("equal", {a.handle()}, kw);
}
inline NDArray equal(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("equal", {a.handle(), b.handle()}, kw);
}
inline NDArray equal(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("equal", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray exp(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("exp", {a.handle()}, kw);
}
inline NDArray exp(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("exp", {a.handle(), b.handle()}, kw);
}
inline NDArray exp(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("exp", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray exp2(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("exp2", {a.handle()}, kw);
}
inline NDArray exp2(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("exp2", {a.handle(), b.handle()}, kw);
}
inline NDArray exp2(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("exp2", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray expand_dims(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("expand_dims", {a.handle()}, kw);
}
inline NDArray expand_dims(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("expand_dims", {a.handle(), b.handle()}, kw);
}
inline NDArray expand_dims(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("expand_dims", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray expm1(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("expm1", {a.handle()}, kw);
}
inline NDArray expm1(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("expm1", {a.handle(), b.handle()}, kw);
}
inline NDArray expm1(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("expm1", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray extract(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("extract", {a.handle()}, kw);
}
inline NDArray extract(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("extract", {a.handle(), b.handle()}, kw);
}
inline NDArray extract(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("extract", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray eye(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("eye", {a.handle()}, kw);
}
inline NDArray eye(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("eye", {a.handle(), b.handle()}, kw);
}
inline NDArray eye(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("eye", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray fabs(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fabs", {a.handle()}, kw);
}
inline NDArray fabs(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fabs", {a.handle(), b.handle()}, kw);
}
inline NDArray fabs(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("fabs", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray fill_diagonal(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fill_diagonal", {a.handle()}, kw);
}
inline NDArray fill_diagonal(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fill_diagonal", {a.handle(), b.handle()}, kw);
}
inline NDArray fill_diagonal(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("fill_diagonal", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray fix(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fix", {a.handle()}, kw);
}
inline NDArray fix(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fix", {a.handle(), b.handle()}, kw);
}
inline NDArray fix(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("fix", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray flatnonzero(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("flatnonzero", {a.handle()}, kw);
}
inline NDArray flatnonzero(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("flatnonzero", {a.handle(), b.handle()}, kw);
}
inline NDArray flatnonzero(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("flatnonzero", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray flip(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("flip", {a.handle()}, kw);
}
inline NDArray flip(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("flip", {a.handle(), b.handle()}, kw);
}
inline NDArray flip(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("flip", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray fliplr(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fliplr", {a.handle()}, kw);
}
inline NDArray fliplr(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fliplr", {a.handle(), b.handle()}, kw);
}
inline NDArray fliplr(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("fliplr", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray flipud(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("flipud", {a.handle()}, kw);
}
inline NDArray flipud(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("flipud", {a.handle(), b.handle()}, kw);
}
inline NDArray flipud(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("flipud", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray float_power(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("float_power", {a.handle()}, kw);
}
inline NDArray float_power(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("float_power", {a.handle(), b.handle()}, kw);
}
inline NDArray float_power(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("float_power", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray floor(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("floor", {a.handle()}, kw);
}
inline NDArray floor(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("floor", {a.handle(), b.handle()}, kw);
}
inline NDArray floor(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("floor", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray floor_divide(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("floor_divide", {a.handle()}, kw);
}
inline NDArray floor_divide(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("floor_divide", {a.handle(), b.handle()}, kw);
}
inline NDArray floor_divide(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("floor_divide", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray fmax(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fmax", {a.handle()}, kw);
}
inline NDArray fmax(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fmax", {a.handle(), b.handle()}, kw);
}
inline NDArray fmax(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("fmax", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray fmin(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fmin", {a.handle()}, kw);
}
inline NDArray fmin(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fmin", {a.handle(), b.handle()}, kw);
}
inline NDArray fmin(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("fmin", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray fmod(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fmod", {a.handle()}, kw);
}
inline NDArray fmod(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fmod", {a.handle(), b.handle()}, kw);
}
inline NDArray fmod(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("fmod", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray fromiter(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fromiter", {a.handle()}, kw);
}
inline NDArray fromiter(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fromiter", {a.handle(), b.handle()}, kw);
}
inline NDArray fromiter(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("fromiter", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray full(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("full", {a.handle()}, kw);
}
inline NDArray full(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("full", {a.handle(), b.handle()}, kw);
}
inline NDArray full(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("full", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray full_like(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("full_like", {a.handle()}, kw);
}
inline NDArray full_like(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("full_like", {a.handle(), b.handle()}, kw);
}
inline NDArray full_like(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("full_like", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray fv(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fv", {a.handle()}, kw);
}
inline NDArray fv(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fv", {a.handle(), b.handle()}, kw);
}
inline NDArray fv(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("fv", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray gcd(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("gcd", {a.handle()}, kw);
}
inline NDArray gcd(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("gcd", {a.handle(), b.handle()}, kw);
}
inline NDArray gcd(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("gcd", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray genfromtxt(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("genfromtxt", {a.handle()}, kw);
}
inline NDArray genfromtxt(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("genfromtxt", {a.handle(), b.handle()}, kw);
}
inline NDArray genfromtxt(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("genfromtxt", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray get_include(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("get_include", {a.handle()}, kw);
}
inline NDArray get_include(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("get_include", {a.handle(), b.handle()}, kw);
}
inline NDArray get_include(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("get_include", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray get_printoptions(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("get_printoptions", {a.handle()}, kw);
}
inline NDArray get_printoptions(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("get_printoptions", {a.handle(), b.handle()}, kw);
}
inline NDArray get_printoptions(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("get_printoptions", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray gradient(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("gradient", {a.handle()}, kw);
}
inline NDArray gradient(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("gradient", {a.handle(), b.handle()}, kw);
}
inline NDArray gradient(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("gradient", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray greater(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("greater", {a.handle()}, kw);
}
inline NDArray greater(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("greater", {a.handle(), b.handle()}, kw);
}
inline NDArray greater(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("greater", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray greater_equal(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("greater_equal", {a.handle()}, kw);
}
inline NDArray greater_equal(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("greater_equal", {a.handle(), b.handle()}, kw);
}
inline NDArray greater_equal(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("greater_equal", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray hamming(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("hamming", {a.handle()}, kw);
}
inline NDArray hamming(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("hamming", {a.handle(), b.handle()}, kw);
}
inline NDArray hamming(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("hamming", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray hanning(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("hanning", {a.handle()}, kw);
}
inline NDArray hanning(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("hanning", {a.handle(), b.handle()}, kw);
}
inline NDArray hanning(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("hanning", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray heaviside(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("heaviside", {a.handle()}, kw);
}
inline NDArray heaviside(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("heaviside", {a.handle(), b.handle()}, kw);
}
inline NDArray heaviside(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("heaviside", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray histogram(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("histogram", {a.handle()}, kw);
}
inline NDArray histogram(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("histogram", {a.handle(), b.handle()}, kw);
}
inline NDArray histogram(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("histogram", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray hsplit(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("hsplit", {a.handle()}, kw);
}
inline NDArray hsplit(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("hsplit", {a.handle(), b.handle()}, kw);
}
inline NDArray hsplit(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("hsplit", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray hstack(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("hstack", {a.handle()}, kw);
}
inline NDArray hstack(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("hstack", {a.handle(), b.handle()}, kw);
}
inline NDArray hstack(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("hstack", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray hypot(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("hypot", {a.handle()}, kw);
}
inline NDArray hypot(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("hypot", {a.handle(), b.handle()}, kw);
}
inline NDArray hypot(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("hypot", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray identity(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("identity", {a.handle()}, kw);
}
inline NDArray identity(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("identity", {a.handle(), b.handle()}, kw);
}
inline NDArray identity(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("identity", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray imag(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("imag", {a.handle()}, kw);
}
inline NDArray imag(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("imag", {a.handle(), b.handle()}, kw);
}
inline NDArray imag(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("imag", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray indices(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("indices", {a.handle()}, kw);
}
inline NDArray indices(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("indices", {a.handle(), b.handle()}, kw);
}
inline NDArray indices(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("indices", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray inner(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("inner", {a.handle()}, kw);
}
inline NDArray inner(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("inner", {a.handle(), b.handle()}, kw);
}
inline NDArray inner(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("inner", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray insert(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("insert", {a.handle()}, kw);
}
inline NDArray insert(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("insert", {a.handle(), b.handle()}, kw);
}
inline NDArray insert(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("insert", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray interp(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("interp", {a.handle()}, kw);
}
inline NDArray interp(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("interp", {a.handle(), b.handle()}, kw);
}
inline NDArray interp(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("interp", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray invert(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("invert", {a.handle()}, kw);
}
inline NDArray invert(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("invert", {a.handle(), b.handle()}, kw);
}
inline NDArray invert(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("invert", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray ipmt(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("ipmt", {a.handle()}, kw);
}
inline NDArray ipmt(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("ipmt", {a.handle(), b.handle()}, kw);
}
inline NDArray ipmt(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("ipmt", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray irr(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("irr", {a.handle()}, kw);
}
inline NDArray irr(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("irr", {a.handle(), b.handle()}, kw);
}
inline NDArray irr(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("irr", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray isclose(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("isclose", {a.handle()}, kw);
}
inline NDArray isclose(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("isclose", {a.handle(), b.handle()}, kw);
}
inline NDArray isclose(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("isclose", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray isfinite(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("isfinite", {a.handle()}, kw);
}
inline NDArray isfinite(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("isfinite", {a.handle(), b.handle()}, kw);
}
inline NDArray isfinite(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("isfinite", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray isinf(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("isinf", {a.handle()}, kw);
}
inline NDArray isinf(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("isinf", {a.handle(), b.handle()}, kw);
}
inline NDArray isinf(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("isinf", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray isnan(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("isnan", {a.handle()}, kw);
}
inline NDArray isnan(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("isnan", {a.handle(), b.handle()}, kw);
}
inline NDArray isnan(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("isnan", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray isneginf(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("isneginf", {a.handle()}, kw);
}
inline NDArray isneginf(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("isneginf", {a.handle(), b.handle()}, kw);
}
inline NDArray isneginf(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("isneginf", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray isposinf(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("isposinf", {a.handle()}, kw);
}
inline NDArray isposinf(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("isposinf", {a.handle(), b.handle()}, kw);
}
inline NDArray isposinf(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("isposinf", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray kron(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("kron", {a.handle()}, kw);
}
inline NDArray kron(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("kron", {a.handle(), b.handle()}, kw);
}
inline NDArray kron(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("kron", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray lcm(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("lcm", {a.handle()}, kw);
}
inline NDArray lcm(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("lcm", {a.handle(), b.handle()}, kw);
}
inline NDArray lcm(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("lcm", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray ldexp(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("ldexp", {a.handle()}, kw);
}
inline NDArray ldexp(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("ldexp", {a.handle(), b.handle()}, kw);
}
inline NDArray ldexp(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("ldexp", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray left_shift(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("left_shift", {a.handle()}, kw);
}
inline NDArray left_shift(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("left_shift", {a.handle(), b.handle()}, kw);
}
inline NDArray left_shift(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("left_shift", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray less(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("less", {a.handle()}, kw);
}
inline NDArray less(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("less", {a.handle(), b.handle()}, kw);
}
inline NDArray less(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("less", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray less_equal(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("less_equal", {a.handle()}, kw);
}
inline NDArray less_equal(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("less_equal", {a.handle(), b.handle()}, kw);
}
inline NDArray less_equal(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("less_equal", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray lexsort(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("lexsort", {a.handle()}, kw);
}
inline NDArray lexsort(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("lexsort", {a.handle(), b.handle()}, kw);
}
inline NDArray lexsort(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("lexsort", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray linspace(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linspace", {a.handle()}, kw);
}
inline NDArray linspace(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linspace", {a.handle(), b.handle()}, kw);
}
inline NDArray linspace(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("linspace", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray load(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("load", {a.handle()}, kw);
}
inline NDArray load(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("load", {a.handle(), b.handle()}, kw);
}
inline NDArray load(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("load", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray log(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("log", {a.handle()}, kw);
}
inline NDArray log(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("log", {a.handle(), b.handle()}, kw);
}
inline NDArray log(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("log", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray log10(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("log10", {a.handle()}, kw);
}
inline NDArray log10(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("log10", {a.handle(), b.handle()}, kw);
}
inline NDArray log10(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("log10", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray log1p(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("log1p", {a.handle()}, kw);
}
inline NDArray log1p(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("log1p", {a.handle(), b.handle()}, kw);
}
inline NDArray log1p(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("log1p", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray log2(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("log2", {a.handle()}, kw);
}
inline NDArray log2(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("log2", {a.handle(), b.handle()}, kw);
}
inline NDArray log2(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("log2", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray logaddexp(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("logaddexp", {a.handle()}, kw);
}
inline NDArray logaddexp(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("logaddexp", {a.handle(), b.handle()}, kw);
}
inline NDArray logaddexp(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("logaddexp", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray logaddexp2(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("logaddexp2", {a.handle()}, kw);
}
inline NDArray logaddexp2(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("logaddexp2", {a.handle(), b.handle()}, kw);
}
inline NDArray logaddexp2(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("logaddexp2", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray logical_and(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("logical_and", {a.handle()}, kw);
}
inline NDArray logical_and(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("logical_and", {a.handle(), b.handle()}, kw);
}
inline NDArray logical_and(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("logical_and", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray logical_not(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("logical_not", {a.handle()}, kw);
}
inline NDArray logical_not(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("logical_not", {a.handle(), b.handle()}, kw);
}
inline NDArray logical_not(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("logical_not", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray logical_or(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("logical_or", {a.handle()}, kw);
}
inline NDArray logical_or(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("logical_or", {a.handle(), b.handle()}, kw);
}
inline NDArray logical_or(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("logical_or", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray logical_xor(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("logical_xor", {a.handle()}, kw);
}
inline NDArray logical_xor(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("logical_xor", {a.handle(), b.handle()}, kw);
}
inline NDArray logical_xor(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("logical_xor", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray logspace(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("logspace", {a.handle()}, kw);
}
inline NDArray logspace(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("logspace", {a.handle(), b.handle()}, kw);
}
inline NDArray logspace(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("logspace", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray matmul(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("matmul", {a.handle()}, kw);
}
inline NDArray matmul(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("matmul", {a.handle(), b.handle()}, kw);
}
inline NDArray matmul(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("matmul", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray matrix_power(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("matrix_power", {a.handle()}, kw);
}
inline NDArray matrix_power(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("matrix_power", {a.handle(), b.handle()}, kw);
}
inline NDArray matrix_power(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("matrix_power", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray max(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("max", {a.handle()}, kw);
}
inline NDArray max(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("max", {a.handle(), b.handle()}, kw);
}
inline NDArray max(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("max", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray maximum(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("maximum", {a.handle()}, kw);
}
inline NDArray maximum(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("maximum", {a.handle(), b.handle()}, kw);
}
inline NDArray maximum(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("maximum", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray may_share_memory(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("may_share_memory", {a.handle()}, kw);
}
inline NDArray may_share_memory(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("may_share_memory", {a.handle(), b.handle()}, kw);
}
inline NDArray may_share_memory(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("may_share_memory", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray mean(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("mean", {a.handle()}, kw);
}
inline NDArray mean(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("mean", {a.handle(), b.handle()}, kw);
}
inline NDArray mean(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("mean", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray median(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("median", {a.handle()}, kw);
}
inline NDArray median(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("median", {a.handle(), b.handle()}, kw);
}
inline NDArray median(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("median", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray meshgrid(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("meshgrid", {a.handle()}, kw);
}
inline NDArray meshgrid(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("meshgrid", {a.handle(), b.handle()}, kw);
}
inline NDArray meshgrid(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("meshgrid", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray min(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("min", {a.handle()}, kw);
}
inline NDArray min(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("min", {a.handle(), b.handle()}, kw);
}
inline NDArray min(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("min", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray minimum(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("minimum", {a.handle()}, kw);
}
inline NDArray minimum(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("minimum", {a.handle(), b.handle()}, kw);
}
inline NDArray minimum(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("minimum", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray mirr(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("mirr", {a.handle()}, kw);
}
inline NDArray mirr(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("mirr", {a.handle(), b.handle()}, kw);
}
inline NDArray mirr(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("mirr", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray mod(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("mod", {a.handle()}, kw);
}
inline NDArray mod(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("mod", {a.handle(), b.handle()}, kw);
}
inline NDArray mod(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("mod", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray moveaxis(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("moveaxis", {a.handle()}, kw);
}
inline NDArray moveaxis(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("moveaxis", {a.handle(), b.handle()}, kw);
}
inline NDArray moveaxis(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("moveaxis", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray msort(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("msort", {a.handle()}, kw);
}
inline NDArray msort(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("msort", {a.handle(), b.handle()}, kw);
}
inline NDArray msort(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("msort", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray multiply(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("multiply", {a.handle()}, kw);
}
inline NDArray multiply(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("multiply", {a.handle(), b.handle()}, kw);
}
inline NDArray multiply(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("multiply", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray nan_to_num(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("nan_to_num", {a.handle()}, kw);
}
inline NDArray nan_to_num(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("nan_to_num", {a.handle(), b.handle()}, kw);
}
inline NDArray nan_to_num(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("nan_to_num", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray nanargmax(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("nanargmax", {a.handle()}, kw);
}
inline NDArray nanargmax(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("nanargmax", {a.handle(), b.handle()}, kw);
}
inline NDArray nanargmax(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("nanargmax", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray nanargmin(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("nanargmin", {a.handle()}, kw);
}
inline NDArray nanargmin(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("nanargmin", {a.handle(), b.handle()}, kw);
}
inline NDArray nanargmin(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("nanargmin", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray nanmax(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("nanmax", {a.handle()}, kw);
}
inline NDArray nanmax(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("nanmax", {a.handle(), b.handle()}, kw);
}
inline NDArray nanmax(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("nanmax", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray nanmean(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("nanmean", {a.handle()}, kw);
}
inline NDArray nanmean(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("nanmean", {a.handle(), b.handle()}, kw);
}
inline NDArray nanmean(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("nanmean", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray nanmedian(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("nanmedian", {a.handle()}, kw);
}
inline NDArray nanmedian(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("nanmedian", {a.handle(), b.handle()}, kw);
}
inline NDArray nanmedian(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("nanmedian", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray nanmin(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("nanmin", {a.handle()}, kw);
}
inline NDArray nanmin(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("nanmin", {a.handle(), b.handle()}, kw);
}
inline NDArray nanmin(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("nanmin", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray nanprod(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("nanprod", {a.handle()}, kw);
}
inline NDArray nanprod(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("nanprod", {a.handle(), b.handle()}, kw);
}
inline NDArray nanprod(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("nanprod", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray nansum(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("nansum", {a.handle()}, kw);
}
inline NDArray nansum(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("nansum", {a.handle(), b.handle()}, kw);
}
inline NDArray nansum(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("nansum", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray narrow_dtype(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("narrow_dtype", {a.handle()}, kw);
}
inline NDArray narrow_dtype(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("narrow_dtype", {a.handle(), b.handle()}, kw);
}
inline NDArray narrow_dtype(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("narrow_dtype", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray ndim(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("ndim", {a.handle()}, kw);
}
inline NDArray ndim(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("ndim", {a.handle(), b.handle()}, kw);
}
inline NDArray ndim(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("ndim", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray negative(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("negative", {a.handle()}, kw);
}
inline NDArray negative(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("negative", {a.handle(), b.handle()}, kw);
}
inline NDArray negative(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("negative", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray nextafter(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("nextafter", {a.handle()}, kw);
}
inline NDArray nextafter(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("nextafter", {a.handle(), b.handle()}, kw);
}
inline NDArray nextafter(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("nextafter", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray nonzero(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("nonzero", {a.handle()}, kw);
}
inline NDArray nonzero(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("nonzero", {a.handle(), b.handle()}, kw);
}
inline NDArray nonzero(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("nonzero", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray not_equal(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("not_equal", {a.handle()}, kw);
}
inline NDArray not_equal(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("not_equal", {a.handle(), b.handle()}, kw);
}
inline NDArray not_equal(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("not_equal", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray nper(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("nper", {a.handle()}, kw);
}
inline NDArray nper(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("nper", {a.handle(), b.handle()}, kw);
}
inline NDArray nper(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("nper", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray npv(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npv", {a.handle()}, kw);
}
inline NDArray npv(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npv", {a.handle(), b.handle()}, kw);
}
inline NDArray npv(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npv", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray ones(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("ones", {a.handle()}, kw);
}
inline NDArray ones(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("ones", {a.handle(), b.handle()}, kw);
}
inline NDArray ones(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("ones", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray ones_like(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("ones_like", {a.handle()}, kw);
}
inline NDArray ones_like(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("ones_like", {a.handle(), b.handle()}, kw);
}
inline NDArray ones_like(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("ones_like", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray outer(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("outer", {a.handle()}, kw);
}
inline NDArray outer(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("outer", {a.handle(), b.handle()}, kw);
}
inline NDArray outer(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("outer", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray pad(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("pad", {a.handle()}, kw);
}
inline NDArray pad(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("pad", {a.handle(), b.handle()}, kw);
}
inline NDArray pad(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("pad", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray partition(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("partition", {a.handle()}, kw);
}
inline NDArray partition(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("partition", {a.handle(), b.handle()}, kw);
}
inline NDArray partition(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("partition", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray percentile(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("percentile", {a.handle()}, kw);
}
inline NDArray percentile(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("percentile", {a.handle(), b.handle()}, kw);
}
inline NDArray percentile(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("percentile", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray permute_dims(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("permute_dims", {a.handle()}, kw);
}
inline NDArray permute_dims(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("permute_dims", {a.handle(), b.handle()}, kw);
}
inline NDArray permute_dims(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("permute_dims", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray pmt(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("pmt", {a.handle()}, kw);
}
inline NDArray pmt(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("pmt", {a.handle(), b.handle()}, kw);
}
inline NDArray pmt(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("pmt", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray polyval(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("polyval", {a.handle()}, kw);
}
inline NDArray polyval(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("polyval", {a.handle(), b.handle()}, kw);
}
inline NDArray polyval(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("polyval", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray positive(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("positive", {a.handle()}, kw);
}
inline NDArray positive(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("positive", {a.handle(), b.handle()}, kw);
}
inline NDArray positive(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("positive", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray power(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("power", {a.handle()}, kw);
}
inline NDArray power(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("power", {a.handle(), b.handle()}, kw);
}
inline NDArray power(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("power", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray ppmt(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("ppmt", {a.handle()}, kw);
}
inline NDArray ppmt(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("ppmt", {a.handle(), b.handle()}, kw);
}
inline NDArray ppmt(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("ppmt", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray prod(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("prod", {a.handle()}, kw);
}
inline NDArray prod(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("prod", {a.handle(), b.handle()}, kw);
}
inline NDArray prod(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("prod", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray product(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("product", {a.handle()}, kw);
}
inline NDArray product(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("product", {a.handle(), b.handle()}, kw);
}
inline NDArray product(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("product", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray promote_types(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("promote_types", {a.handle()}, kw);
}
inline NDArray promote_types(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("promote_types", {a.handle(), b.handle()}, kw);
}
inline NDArray promote_types(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("promote_types", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray ptp(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("ptp", {a.handle()}, kw);
}
inline NDArray ptp(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("ptp", {a.handle(), b.handle()}, kw);
}
inline NDArray ptp(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("ptp", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray put_along_axis(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("put_along_axis", {a.handle()}, kw);
}
inline NDArray put_along_axis(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("put_along_axis", {a.handle(), b.handle()}, kw);
}
inline NDArray put_along_axis(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("put_along_axis", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray pv(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("pv", {a.handle()}, kw);
}
inline NDArray pv(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("pv", {a.handle(), b.handle()}, kw);
}
inline NDArray pv(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("pv", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray quantile(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("quantile", {a.handle()}, kw);
}
inline NDArray quantile(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("quantile", {a.handle(), b.handle()}, kw);
}
inline NDArray quantile(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("quantile", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray rad2deg(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("rad2deg", {a.handle()}, kw);
}
inline NDArray rad2deg(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("rad2deg", {a.handle(), b.handle()}, kw);
}
inline NDArray rad2deg(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("rad2deg", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray radians(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("radians", {a.handle()}, kw);
}
inline NDArray radians(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("radians", {a.handle(), b.handle()}, kw);
}
inline NDArray radians(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("radians", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray rate(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("rate", {a.handle()}, kw);
}
inline NDArray rate(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("rate", {a.handle(), b.handle()}, kw);
}
inline NDArray rate(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("rate", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray ravel(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("ravel", {a.handle()}, kw);
}
inline NDArray ravel(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("ravel", {a.handle(), b.handle()}, kw);
}
inline NDArray ravel(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("ravel", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray ravel_multi_index(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("ravel_multi_index", {a.handle()}, kw);
}
inline NDArray ravel_multi_index(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("ravel_multi_index", {a.handle(), b.handle()}, kw);
}
inline NDArray ravel_multi_index(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("ravel_multi_index", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray real(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("real", {a.handle()}, kw);
}
inline NDArray real(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("real", {a.handle(), b.handle()}, kw);
}
inline NDArray real(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("real", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray reciprocal(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("reciprocal", {a.handle()}, kw);
}
inline NDArray reciprocal(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("reciprocal", {a.handle(), b.handle()}, kw);
}
inline NDArray reciprocal(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("reciprocal", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray remainder(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("remainder", {a.handle()}, kw);
}
inline NDArray remainder(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("remainder", {a.handle(), b.handle()}, kw);
}
inline NDArray remainder(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("remainder", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray repeat(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("repeat", {a.handle()}, kw);
}
inline NDArray repeat(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("repeat", {a.handle(), b.handle()}, kw);
}
inline NDArray repeat(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("repeat", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray reshape(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("reshape", {a.handle()}, kw);
}
inline NDArray reshape(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("reshape", {a.handle(), b.handle()}, kw);
}
inline NDArray reshape(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("reshape", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray resize(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("resize", {a.handle()}, kw);
}
inline NDArray resize(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("resize", {a.handle(), b.handle()}, kw);
}
inline NDArray resize(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("resize", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray resolve_dtype(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("resolve_dtype", {a.handle()}, kw);
}
inline NDArray resolve_dtype(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("resolve_dtype", {a.handle(), b.handle()}, kw);
}
inline NDArray resolve_dtype(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("resolve_dtype", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray result_type(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("result_type", {a.handle()}, kw);
}
inline NDArray result_type(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("result_type", {a.handle(), b.handle()}, kw);
}
inline NDArray result_type(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("result_type", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray right_shift(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("right_shift", {a.handle()}, kw);
}
inline NDArray right_shift(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("right_shift", {a.handle(), b.handle()}, kw);
}
inline NDArray right_shift(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("right_shift", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray rint(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("rint", {a.handle()}, kw);
}
inline NDArray rint(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("rint", {a.handle(), b.handle()}, kw);
}
inline NDArray rint(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("rint", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray roll(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("roll", {a.handle()}, kw);
}
inline NDArray roll(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("roll", {a.handle(), b.handle()}, kw);
}
inline NDArray roll(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("roll", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray rollaxis(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("rollaxis", {a.handle()}, kw);
}
inline NDArray rollaxis(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("rollaxis", {a.handle(), b.handle()}, kw);
}
inline NDArray rollaxis(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("rollaxis", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray rot90(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("rot90", {a.handle()}, kw);
}
inline NDArray rot90(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("rot90", {a.handle(), b.handle()}, kw);
}
inline NDArray rot90(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("rot90", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray round(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("round", {a.handle()}, kw);
}
inline NDArray round(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("round", {a.handle(), b.handle()}, kw);
}
inline NDArray round(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("round", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray round_(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("round_", {a.handle()}, kw);
}
inline NDArray round_(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("round_", {a.handle(), b.handle()}, kw);
}
inline NDArray round_(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("round_", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray row_stack(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("row_stack", {a.handle()}, kw);
}
inline NDArray row_stack(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("row_stack", {a.handle(), b.handle()}, kw);
}
inline NDArray row_stack(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("row_stack", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray save(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("save", {a.handle()}, kw);
}
inline NDArray save(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("save", {a.handle(), b.handle()}, kw);
}
inline NDArray save(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("save", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray savez(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("savez", {a.handle()}, kw);
}
inline NDArray savez(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("savez", {a.handle(), b.handle()}, kw);
}
inline NDArray savez(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("savez", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray searchsorted(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("searchsorted", {a.handle()}, kw);
}
inline NDArray searchsorted(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("searchsorted", {a.handle(), b.handle()}, kw);
}
inline NDArray searchsorted(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("searchsorted", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray set_printoptions(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("set_printoptions", {a.handle()}, kw);
}
inline NDArray set_printoptions(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("set_printoptions", {a.handle(), b.handle()}, kw);
}
inline NDArray set_printoptions(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("set_printoptions", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray shape(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("shape", {a.handle()}, kw);
}
inline NDArray shape(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("shape", {a.handle(), b.handle()}, kw);
}
inline NDArray shape(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("shape", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray shares_memory(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("shares_memory", {a.handle()}, kw);
}
inline NDArray shares_memory(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("shares_memory", {a.handle(), b.handle()}, kw);
}
inline NDArray shares_memory(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("shares_memory", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray sign(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("sign", {a.handle()}, kw);
}
inline NDArray sign(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("sign", {a.handle(), b.handle()}, kw);
}
inline NDArray sign(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("sign", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray signbit(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("signbit", {a.handle()}, kw);
}
inline NDArray signbit(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("signbit", {a.handle(), b.handle()}, kw);
}
inline NDArray signbit(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("signbit", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray sin(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("sin", {a.handle()}, kw);
}
inline NDArray sin(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("sin", {a.handle(), b.handle()}, kw);
}
inline NDArray sin(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("sin", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray sinh(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("sinh", {a.handle()}, kw);
}
inline NDArray sinh(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("sinh", {a.handle(), b.handle()}, kw);
}
inline NDArray sinh(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("sinh", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray size(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("size", {a.handle()}, kw);
}
inline NDArray size(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("size", {a.handle(), b.handle()}, kw);
}
inline NDArray size(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("size", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray sometrue(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("sometrue", {a.handle()}, kw);
}
inline NDArray sometrue(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("sometrue", {a.handle(), b.handle()}, kw);
}
inline NDArray sometrue(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("sometrue", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray sort(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("sort", {a.handle()}, kw);
}
inline NDArray sort(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("sort", {a.handle(), b.handle()}, kw);
}
inline NDArray sort(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("sort", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray split(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("split", {a.handle()}, kw);
}
inline NDArray split(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("split", {a.handle(), b.handle()}, kw);
}
inline NDArray split(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("split", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray sqrt(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("sqrt", {a.handle()}, kw);
}
inline NDArray sqrt(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("sqrt", {a.handle(), b.handle()}, kw);
}
inline NDArray sqrt(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("sqrt", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray square(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("square", {a.handle()}, kw);
}
inline NDArray square(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("square", {a.handle(), b.handle()}, kw);
}
inline NDArray square(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("square", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray squeeze(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("squeeze", {a.handle()}, kw);
}
inline NDArray squeeze(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("squeeze", {a.handle(), b.handle()}, kw);
}
inline NDArray squeeze(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("squeeze", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray stack(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("stack", {a.handle()}, kw);
}
inline NDArray stack(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("stack", {a.handle(), b.handle()}, kw);
}
inline NDArray stack(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("stack", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray std(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("std", {a.handle()}, kw);
}
inline NDArray std(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("std", {a.handle(), b.handle()}, kw);
}
inline NDArray std(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("std", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray subtract(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("subtract", {a.handle()}, kw);
}
inline NDArray subtract(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("subtract", {a.handle(), b.handle()}, kw);
}
inline NDArray subtract(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("subtract", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray sum(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("sum", {a.handle()}, kw);
}
inline NDArray sum(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("sum", {a.handle(), b.handle()}, kw);
}
inline NDArray sum(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("sum", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray swapaxes(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("swapaxes", {a.handle()}, kw);
}
inline NDArray swapaxes(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("swapaxes", {a.handle(), b.handle()}, kw);
}
inline NDArray swapaxes(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("swapaxes", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray take(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("take", {a.handle()}, kw);
}
inline NDArray take(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("take", {a.handle(), b.handle()}, kw);
}
inline NDArray take(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("take", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray take_along_axis(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("take_along_axis", {a.handle()}, kw);
}
inline NDArray take_along_axis(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("take_along_axis", {a.handle(), b.handle()}, kw);
}
inline NDArray take_along_axis(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("take_along_axis", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray tan(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("tan", {a.handle()}, kw);
}
inline NDArray tan(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("tan", {a.handle(), b.handle()}, kw);
}
inline NDArray tan(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("tan", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray tanh(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("tanh", {a.handle()}, kw);
}
inline NDArray tanh(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("tanh", {a.handle(), b.handle()}, kw);
}
inline NDArray tanh(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("tanh", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray tensordot(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("tensordot", {a.handle()}, kw);
}
inline NDArray tensordot(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("tensordot", {a.handle(), b.handle()}, kw);
}
inline NDArray tensordot(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("tensordot", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray tile(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("tile", {a.handle()}, kw);
}
inline NDArray tile(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("tile", {a.handle(), b.handle()}, kw);
}
inline NDArray tile(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("tile", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray trace(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("trace", {a.handle()}, kw);
}
inline NDArray trace(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("trace", {a.handle(), b.handle()}, kw);
}
inline NDArray trace(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("trace", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray transpose(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("transpose", {a.handle()}, kw);
}
inline NDArray transpose(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("transpose", {a.handle(), b.handle()}, kw);
}
inline NDArray transpose(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("transpose", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray tri(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("tri", {a.handle()}, kw);
}
inline NDArray tri(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("tri", {a.handle(), b.handle()}, kw);
}
inline NDArray tri(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("tri", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray tril(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("tril", {a.handle()}, kw);
}
inline NDArray tril(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("tril", {a.handle(), b.handle()}, kw);
}
inline NDArray tril(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("tril", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray tril_indices(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("tril_indices", {a.handle()}, kw);
}
inline NDArray tril_indices(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("tril_indices", {a.handle(), b.handle()}, kw);
}
inline NDArray tril_indices(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("tril_indices", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray tril_indices_from(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("tril_indices_from", {a.handle()}, kw);
}
inline NDArray tril_indices_from(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("tril_indices_from", {a.handle(), b.handle()}, kw);
}
inline NDArray tril_indices_from(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("tril_indices_from", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray trim_zeros(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("trim_zeros", {a.handle()}, kw);
}
inline NDArray trim_zeros(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("trim_zeros", {a.handle(), b.handle()}, kw);
}
inline NDArray trim_zeros(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("trim_zeros", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray triu(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("triu", {a.handle()}, kw);
}
inline NDArray triu(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("triu", {a.handle(), b.handle()}, kw);
}
inline NDArray triu(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("triu", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray triu_indices(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("triu_indices", {a.handle()}, kw);
}
inline NDArray triu_indices(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("triu_indices", {a.handle(), b.handle()}, kw);
}
inline NDArray triu_indices(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("triu_indices", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray triu_indices_from(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("triu_indices_from", {a.handle()}, kw);
}
inline NDArray triu_indices_from(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("triu_indices_from", {a.handle(), b.handle()}, kw);
}
inline NDArray triu_indices_from(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("triu_indices_from", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray true_divide(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("true_divide", {a.handle()}, kw);
}
inline NDArray true_divide(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("true_divide", {a.handle(), b.handle()}, kw);
}
inline NDArray true_divide(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("true_divide", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray trunc(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("trunc", {a.handle()}, kw);
}
inline NDArray trunc(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("trunc", {a.handle(), b.handle()}, kw);
}
inline NDArray trunc(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("trunc", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray unique(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("unique", {a.handle()}, kw);
}
inline NDArray unique(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("unique", {a.handle(), b.handle()}, kw);
}
inline NDArray unique(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("unique", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray unravel_index(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("unravel_index", {a.handle()}, kw);
}
inline NDArray unravel_index(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("unravel_index", {a.handle(), b.handle()}, kw);
}
inline NDArray unravel_index(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("unravel_index", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray vander(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("vander", {a.handle()}, kw);
}
inline NDArray vander(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("vander", {a.handle(), b.handle()}, kw);
}
inline NDArray vander(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("vander", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray var(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("var", {a.handle()}, kw);
}
inline NDArray var(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("var", {a.handle(), b.handle()}, kw);
}
inline NDArray var(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("var", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray vdot(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("vdot", {a.handle()}, kw);
}
inline NDArray vdot(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("vdot", {a.handle(), b.handle()}, kw);
}
inline NDArray vdot(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("vdot", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray vsplit(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("vsplit", {a.handle()}, kw);
}
inline NDArray vsplit(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("vsplit", {a.handle(), b.handle()}, kw);
}
inline NDArray vsplit(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("vsplit", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray vstack(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("vstack", {a.handle()}, kw);
}
inline NDArray vstack(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("vstack", {a.handle(), b.handle()}, kw);
}
inline NDArray vstack(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("vstack", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray where(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("where", {a.handle()}, kw);
}
inline NDArray where(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("where", {a.handle(), b.handle()}, kw);
}
inline NDArray where(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("where", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray zeros(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("zeros", {a.handle()}, kw);
}
inline NDArray zeros(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("zeros", {a.handle(), b.handle()}, kw);
}
inline NDArray zeros(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("zeros", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray zeros_like(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("zeros_like", {a.handle()}, kw);
}
inline NDArray zeros_like(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("zeros_like", {a.handle(), b.handle()}, kw);
}
inline NDArray zeros_like(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("zeros_like", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray activation(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:activation", {a.handle()}, kw);
}
inline NDArray activation(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:activation", {a.handle(), b.handle()}, kw);
}
inline NDArray activation(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:activation", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray adaptive_avg_pool2d(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:adaptive_avg_pool2d", {a.handle()}, kw);
}
inline NDArray adaptive_avg_pool2d(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:adaptive_avg_pool2d", {a.handle(), b.handle()}, kw);
}
inline NDArray adaptive_avg_pool2d(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:adaptive_avg_pool2d", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray all_finite(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:all_finite", {a.handle()}, kw);
}
inline NDArray all_finite(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:all_finite", {a.handle(), b.handle()}, kw);
}
inline NDArray all_finite(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:all_finite", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray arange_like(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:arange_like", {a.handle()}, kw);
}
inline NDArray arange_like(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:arange_like", {a.handle(), b.handle()}, kw);
}
inline NDArray arange_like(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:arange_like", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray batch_dot(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:batch_dot", {a.handle()}, kw);
}
inline NDArray batch_dot(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:batch_dot", {a.handle(), b.handle()}, kw);
}
inline NDArray batch_dot(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:batch_dot", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray batch_norm(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:batch_norm", {a.handle()}, kw);
}
inline NDArray batch_norm(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:batch_norm", {a.handle(), b.handle()}, kw);
}
inline NDArray batch_norm(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:batch_norm", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray bilinear_resize2d(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:bilinear_resize2d", {a.handle()}, kw);
}
inline NDArray bilinear_resize2d(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:bilinear_resize2d", {a.handle(), b.handle()}, kw);
}
inline NDArray bilinear_resize2d(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:bilinear_resize2d", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray bilinear_sampler(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:bilinear_sampler", {a.handle()}, kw);
}
inline NDArray bilinear_sampler(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:bilinear_sampler", {a.handle(), b.handle()}, kw);
}
inline NDArray bilinear_sampler(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:bilinear_sampler", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray bipartite_matching(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:bipartite_matching", {a.handle()}, kw);
}
inline NDArray bipartite_matching(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:bipartite_matching", {a.handle(), b.handle()}, kw);
}
inline NDArray bipartite_matching(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:bipartite_matching", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray boolean_mask(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:boolean_mask", {a.handle()}, kw);
}
inline NDArray boolean_mask(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:boolean_mask", {a.handle(), b.handle()}, kw);
}
inline NDArray boolean_mask(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:boolean_mask", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray box_decode(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:box_decode", {a.handle()}, kw);
}
inline NDArray box_decode(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:box_decode", {a.handle(), b.handle()}, kw);
}
inline NDArray box_decode(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:box_decode", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray box_encode(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:box_encode", {a.handle()}, kw);
}
inline NDArray box_encode(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:box_encode", {a.handle(), b.handle()}, kw);
}
inline NDArray box_encode(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:box_encode", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray box_iou(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:box_iou", {a.handle()}, kw);
}
inline NDArray box_iou(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:box_iou", {a.handle(), b.handle()}, kw);
}
inline NDArray box_iou(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:box_iou", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray box_nms(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:box_nms", {a.handle()}, kw);
}
inline NDArray box_nms(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:box_nms", {a.handle(), b.handle()}, kw);
}
inline NDArray box_nms(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:box_nms", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray broadcast_like(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:broadcast_like", {a.handle()}, kw);
}
inline NDArray broadcast_like(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:broadcast_like", {a.handle(), b.handle()}, kw);
}
inline NDArray broadcast_like(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:broadcast_like", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray col2im(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:col2im", {a.handle()}, kw);
}
inline NDArray col2im(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:col2im", {a.handle(), b.handle()}, kw);
}
inline NDArray col2im(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:col2im", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray cond(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:cond", {a.handle()}, kw);
}
inline NDArray cond(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:cond", {a.handle(), b.handle()}, kw);
}
inline NDArray cond(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:cond", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray constraint_check(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:constraint_check", {a.handle()}, kw);
}
inline NDArray constraint_check(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:constraint_check", {a.handle(), b.handle()}, kw);
}
inline NDArray constraint_check(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:constraint_check", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray convolution(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:convolution", {a.handle()}, kw);
}
inline NDArray convolution(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:convolution", {a.handle(), b.handle()}, kw);
}
inline NDArray convolution(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:convolution", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray correlation(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:correlation", {a.handle()}, kw);
}
inline NDArray correlation(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:correlation", {a.handle(), b.handle()}, kw);
}
inline NDArray correlation(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:correlation", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray count_sketch(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:count_sketch", {a.handle()}, kw);
}
inline NDArray count_sketch(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:count_sketch", {a.handle(), b.handle()}, kw);
}
inline NDArray count_sketch(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:count_sketch", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray ctc_loss(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:ctc_loss", {a.handle()}, kw);
}
inline NDArray ctc_loss(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:ctc_loss", {a.handle(), b.handle()}, kw);
}
inline NDArray ctc_loss(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:ctc_loss", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray current_device(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:current_device", {a.handle()}, kw);
}
inline NDArray current_device(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:current_device", {a.handle(), b.handle()}, kw);
}
inline NDArray current_device(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:current_device", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray custom(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:custom", {a.handle()}, kw);
}
inline NDArray custom(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:custom", {a.handle(), b.handle()}, kw);
}
inline NDArray custom(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:custom", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray decode_attention(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:decode_attention", {a.handle()}, kw);
}
inline NDArray decode_attention(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:decode_attention", {a.handle(), b.handle()}, kw);
}
inline NDArray decode_attention(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:decode_attention", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray deconvolution(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:deconvolution", {a.handle()}, kw);
}
inline NDArray deconvolution(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:deconvolution", {a.handle(), b.handle()}, kw);
}
inline NDArray deconvolution(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:deconvolution", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray deformable_convolution(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:deformable_convolution", {a.handle()}, kw);
}
inline NDArray deformable_convolution(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:deformable_convolution", {a.handle(), b.handle()}, kw);
}
inline NDArray deformable_convolution(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:deformable_convolution", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray deformable_psroi_pooling(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:deformable_psroi_pooling", {a.handle()}, kw);
}
inline NDArray deformable_psroi_pooling(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:deformable_psroi_pooling", {a.handle(), b.handle()}, kw);
}
inline NDArray deformable_psroi_pooling(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:deformable_psroi_pooling", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray depth_to_space(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:depth_to_space", {a.handle()}, kw);
}
inline NDArray depth_to_space(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:depth_to_space", {a.handle(), b.handle()}, kw);
}
inline NDArray depth_to_space(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:depth_to_space", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray digamma(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:digamma", {a.handle()}, kw);
}
inline NDArray digamma(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:digamma", {a.handle(), b.handle()}, kw);
}
inline NDArray digamma(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:digamma", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray div_sqrt_dim(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:div_sqrt_dim", {a.handle()}, kw);
}
inline NDArray div_sqrt_dim(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:div_sqrt_dim", {a.handle(), b.handle()}, kw);
}
inline NDArray div_sqrt_dim(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:div_sqrt_dim", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray dropout(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:dropout", {a.handle()}, kw);
}
inline NDArray dropout(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:dropout", {a.handle(), b.handle()}, kw);
}
inline NDArray dropout(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:dropout", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray embedding(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:embedding", {a.handle()}, kw);
}
inline NDArray embedding(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:embedding", {a.handle(), b.handle()}, kw);
}
inline NDArray embedding(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:embedding", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray erf(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:erf", {a.handle()}, kw);
}
inline NDArray erf(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:erf", {a.handle(), b.handle()}, kw);
}
inline NDArray erf(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:erf", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray erfinv(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:erfinv", {a.handle()}, kw);
}
inline NDArray erfinv(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:erfinv", {a.handle(), b.handle()}, kw);
}
inline NDArray erfinv(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:erfinv", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray flash_attention(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:flash_attention", {a.handle()}, kw);
}
inline NDArray flash_attention(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:flash_attention", {a.handle(), b.handle()}, kw);
}
inline NDArray flash_attention(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:flash_attention", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray foreach(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:foreach", {a.handle()}, kw);
}
inline NDArray foreach(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:foreach", {a.handle(), b.handle()}, kw);
}
inline NDArray foreach(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:foreach", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray fully_connected(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:fully_connected", {a.handle()}, kw);
}
inline NDArray fully_connected(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:fully_connected", {a.handle(), b.handle()}, kw);
}
inline NDArray fully_connected(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:fully_connected", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray gamma(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:gamma", {a.handle()}, kw);
}
inline NDArray gamma(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:gamma", {a.handle(), b.handle()}, kw);
}
inline NDArray gamma(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:gamma", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray gammaln(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:gammaln", {a.handle()}, kw);
}
inline NDArray gammaln(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:gammaln", {a.handle(), b.handle()}, kw);
}
inline NDArray gammaln(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:gammaln", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray gather_nd(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:gather_nd", {a.handle()}, kw);
}
inline NDArray gather_nd(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:gather_nd", {a.handle(), b.handle()}, kw);
}
inline NDArray gather_nd(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:gather_nd", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray gelu(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:gelu", {a.handle()}, kw);
}
inline NDArray gelu(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:gelu", {a.handle(), b.handle()}, kw);
}
inline NDArray gelu(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:gelu", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray grid_generator(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:grid_generator", {a.handle()}, kw);
}
inline NDArray grid_generator(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:grid_generator", {a.handle(), b.handle()}, kw);
}
inline NDArray grid_generator(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:grid_generator", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray group_norm(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:group_norm", {a.handle()}, kw);
}
inline NDArray group_norm(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:group_norm", {a.handle(), b.handle()}, kw);
}
inline NDArray group_norm(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:group_norm", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray hard_sigmoid(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:hard_sigmoid", {a.handle()}, kw);
}
inline NDArray hard_sigmoid(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:hard_sigmoid", {a.handle(), b.handle()}, kw);
}
inline NDArray hard_sigmoid(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:hard_sigmoid", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray hard_swish(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:hard_swish", {a.handle()}, kw);
}
inline NDArray hard_swish(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:hard_swish", {a.handle(), b.handle()}, kw);
}
inline NDArray hard_swish(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:hard_swish", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray hawkesll(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:hawkesll", {a.handle()}, kw);
}
inline NDArray hawkesll(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:hawkesll", {a.handle(), b.handle()}, kw);
}
inline NDArray hawkesll(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:hawkesll", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray identity_attach_kl_sparse_reg(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:identity_attach_kl_sparse_reg", {a.handle()}, kw);
}
inline NDArray identity_attach_kl_sparse_reg(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:identity_attach_kl_sparse_reg", {a.handle(), b.handle()}, kw);
}
inline NDArray identity_attach_kl_sparse_reg(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:identity_attach_kl_sparse_reg", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray im2col(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:im2col", {a.handle()}, kw);
}
inline NDArray im2col(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:im2col", {a.handle(), b.handle()}, kw);
}
inline NDArray im2col(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:im2col", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray index_add(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:index_add", {a.handle()}, kw);
}
inline NDArray index_add(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:index_add", {a.handle(), b.handle()}, kw);
}
inline NDArray index_add(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:index_add", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray index_array(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:index_array", {a.handle()}, kw);
}
inline NDArray index_array(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:index_array", {a.handle(), b.handle()}, kw);
}
inline NDArray index_array(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:index_array", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray index_copy(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:index_copy", {a.handle()}, kw);
}
inline NDArray index_copy(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:index_copy", {a.handle(), b.handle()}, kw);
}
inline NDArray index_copy(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:index_copy", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray index_update(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:index_update", {a.handle()}, kw);
}
inline NDArray index_update(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:index_update", {a.handle(), b.handle()}, kw);
}
inline NDArray index_update(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:index_update", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray instance_norm(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:instance_norm", {a.handle()}, kw);
}
inline NDArray instance_norm(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:instance_norm", {a.handle(), b.handle()}, kw);
}
inline NDArray instance_norm(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:instance_norm", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray interleaved_matmul_encdec_qk(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:interleaved_matmul_encdec_qk", {a.handle()}, kw);
}
inline NDArray interleaved_matmul_encdec_qk(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:interleaved_matmul_encdec_qk", {a.handle(), b.handle()}, kw);
}
inline NDArray interleaved_matmul_encdec_qk(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:interleaved_matmul_encdec_qk", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray interleaved_matmul_encdec_valatt(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:interleaved_matmul_encdec_valatt", {a.handle()}, kw);
}
inline NDArray interleaved_matmul_encdec_valatt(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:interleaved_matmul_encdec_valatt", {a.handle(), b.handle()}, kw);
}
inline NDArray interleaved_matmul_encdec_valatt(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:interleaved_matmul_encdec_valatt", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray interleaved_matmul_selfatt_qk(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:interleaved_matmul_selfatt_qk", {a.handle()}, kw);
}
inline NDArray interleaved_matmul_selfatt_qk(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:interleaved_matmul_selfatt_qk", {a.handle(), b.handle()}, kw);
}
inline NDArray interleaved_matmul_selfatt_qk(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:interleaved_matmul_selfatt_qk", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray interleaved_matmul_selfatt_valatt(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:interleaved_matmul_selfatt_valatt", {a.handle()}, kw);
}
inline NDArray interleaved_matmul_selfatt_valatt(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:interleaved_matmul_selfatt_valatt", {a.handle(), b.handle()}, kw);
}
inline NDArray interleaved_matmul_selfatt_valatt(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:interleaved_matmul_selfatt_valatt", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray is_np_array(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:is_np_array", {a.handle()}, kw);
}
inline NDArray is_np_array(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:is_np_array", {a.handle(), b.handle()}, kw);
}
inline NDArray is_np_array(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:is_np_array", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray is_np_shape(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:is_np_shape", {a.handle()}, kw);
}
inline NDArray is_np_shape(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:is_np_shape", {a.handle(), b.handle()}, kw);
}
inline NDArray is_np_shape(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:is_np_shape", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray khatri_rao(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:khatri_rao", {a.handle()}, kw);
}
inline NDArray khatri_rao(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:khatri_rao", {a.handle(), b.handle()}, kw);
}
inline NDArray khatri_rao(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:khatri_rao", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray l2_normalization(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:l2_normalization", {a.handle()}, kw);
}
inline NDArray l2_normalization(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:l2_normalization", {a.handle(), b.handle()}, kw);
}
inline NDArray l2_normalization(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:l2_normalization", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray layer_norm(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:layer_norm", {a.handle()}, kw);
}
inline NDArray layer_norm(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:layer_norm", {a.handle(), b.handle()}, kw);
}
inline NDArray layer_norm(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:layer_norm", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray leaky_relu(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:leaky_relu", {a.handle()}, kw);
}
inline NDArray leaky_relu(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:leaky_relu", {a.handle(), b.handle()}, kw);
}
inline NDArray leaky_relu(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:leaky_relu", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray linear_regression_output(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:linear_regression_output", {a.handle()}, kw);
}
inline NDArray linear_regression_output(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:linear_regression_output", {a.handle(), b.handle()}, kw);
}
inline NDArray linear_regression_output(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:linear_regression_output", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray log_sigmoid(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:log_sigmoid", {a.handle()}, kw);
}
inline NDArray log_sigmoid(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:log_sigmoid", {a.handle(), b.handle()}, kw);
}
inline NDArray log_sigmoid(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:log_sigmoid", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray log_softmax(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:log_softmax", {a.handle()}, kw);
}
inline NDArray log_softmax(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:log_softmax", {a.handle(), b.handle()}, kw);
}
inline NDArray log_softmax(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:log_softmax", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray logistic_regression_output(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:logistic_regression_output", {a.handle()}, kw);
}
inline NDArray logistic_regression_output(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:logistic_regression_output", {a.handle(), b.handle()}, kw);
}
inline NDArray logistic_regression_output(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:logistic_regression_output", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray lrn(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:lrn", {a.handle()}, kw);
}
inline NDArray lrn(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:lrn", {a.handle(), b.handle()}, kw);
}
inline NDArray lrn(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:lrn", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray mae_regression_output(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:mae_regression_output", {a.handle()}, kw);
}
inline NDArray mae_regression_output(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:mae_regression_output", {a.handle(), b.handle()}, kw);
}
inline NDArray mae_regression_output(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:mae_regression_output", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray make_loss(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:make_loss", {a.handle()}, kw);
}
inline NDArray make_loss(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:make_loss", {a.handle(), b.handle()}, kw);
}
inline NDArray make_loss(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:make_loss", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray masked_log_softmax(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:masked_log_softmax", {a.handle()}, kw);
}
inline NDArray masked_log_softmax(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:masked_log_softmax", {a.handle(), b.handle()}, kw);
}
inline NDArray masked_log_softmax(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:masked_log_softmax", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray masked_softmax(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:masked_softmax", {a.handle()}, kw);
}
inline NDArray masked_softmax(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:masked_softmax", {a.handle(), b.handle()}, kw);
}
inline NDArray masked_softmax(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:masked_softmax", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray mish(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:mish", {a.handle()}, kw);
}
inline NDArray mish(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:mish", {a.handle(), b.handle()}, kw);
}
inline NDArray mish(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:mish", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray modulated_deformable_convolution(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:modulated_deformable_convolution", {a.handle()}, kw);
}
inline NDArray modulated_deformable_convolution(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:modulated_deformable_convolution", {a.handle(), b.handle()}, kw);
}
inline NDArray modulated_deformable_convolution(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:modulated_deformable_convolution", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray moments(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:moments", {a.handle()}, kw);
}
inline NDArray moments(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:moments", {a.handle(), b.handle()}, kw);
}
inline NDArray moments(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:moments", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray multi_all_finite(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multi_all_finite", {a.handle()}, kw);
}
inline NDArray multi_all_finite(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multi_all_finite", {a.handle(), b.handle()}, kw);
}
inline NDArray multi_all_finite(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multi_all_finite", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray multi_proposal(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multi_proposal", {a.handle()}, kw);
}
inline NDArray multi_proposal(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multi_proposal", {a.handle(), b.handle()}, kw);
}
inline NDArray multi_proposal(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multi_proposal", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray multi_sum_sq(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multi_sum_sq", {a.handle()}, kw);
}
inline NDArray multi_sum_sq(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multi_sum_sq", {a.handle(), b.handle()}, kw);
}
inline NDArray multi_sum_sq(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multi_sum_sq", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray multibox_detection(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multibox_detection", {a.handle()}, kw);
}
inline NDArray multibox_detection(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multibox_detection", {a.handle(), b.handle()}, kw);
}
inline NDArray multibox_detection(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multibox_detection", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray multibox_prior(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multibox_prior", {a.handle()}, kw);
}
inline NDArray multibox_prior(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multibox_prior", {a.handle(), b.handle()}, kw);
}
inline NDArray multibox_prior(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multibox_prior", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray multibox_target(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multibox_target", {a.handle()}, kw);
}
inline NDArray multibox_target(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multibox_target", {a.handle(), b.handle()}, kw);
}
inline NDArray multibox_target(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:multibox_target", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray next_key(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:next_key", {a.handle()}, kw);
}
inline NDArray next_key(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:next_key", {a.handle(), b.handle()}, kw);
}
inline NDArray next_key(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:next_key", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray num_gpus(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:num_gpus", {a.handle()}, kw);
}
inline NDArray num_gpus(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:num_gpus", {a.handle(), b.handle()}, kw);
}
inline NDArray num_gpus(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:num_gpus", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray one_hot(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:one_hot", {a.handle()}, kw);
}
inline NDArray one_hot(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:one_hot", {a.handle(), b.handle()}, kw);
}
inline NDArray one_hot(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:one_hot", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray pick(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:pick", {a.handle()}, kw);
}
inline NDArray pick(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:pick", {a.handle(), b.handle()}, kw);
}
inline NDArray pick(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:pick", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray pooling(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:pooling", {a.handle()}, kw);
}
inline NDArray pooling(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:pooling", {a.handle(), b.handle()}, kw);
}
inline NDArray pooling(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:pooling", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray proposal(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:proposal", {a.handle()}, kw);
}
inline NDArray proposal(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:proposal", {a.handle(), b.handle()}, kw);
}
inline NDArray proposal(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:proposal", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray quadratic(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:quadratic", {a.handle()}, kw);
}
inline NDArray quadratic(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:quadratic", {a.handle(), b.handle()}, kw);
}
inline NDArray quadratic(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:quadratic", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray rcbrt(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:rcbrt", {a.handle()}, kw);
}
inline NDArray rcbrt(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:rcbrt", {a.handle(), b.handle()}, kw);
}
inline NDArray rcbrt(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:rcbrt", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray relu(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:relu", {a.handle()}, kw);
}
inline NDArray relu(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:relu", {a.handle(), b.handle()}, kw);
}
inline NDArray relu(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:relu", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray reset_np(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:reset_np", {a.handle()}, kw);
}
inline NDArray reset_np(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:reset_np", {a.handle(), b.handle()}, kw);
}
inline NDArray reset_np(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:reset_np", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray reshape_like(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:reshape_like", {a.handle()}, kw);
}
inline NDArray reshape_like(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:reshape_like", {a.handle(), b.handle()}, kw);
}
inline NDArray reshape_like(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:reshape_like", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray ring_attention(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:ring_attention", {a.handle()}, kw);
}
inline NDArray ring_attention(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:ring_attention", {a.handle(), b.handle()}, kw);
}
inline NDArray ring_attention(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:ring_attention", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray rms_norm(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:rms_norm", {a.handle()}, kw);
}
inline NDArray rms_norm(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:rms_norm", {a.handle(), b.handle()}, kw);
}
inline NDArray rms_norm(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:rms_norm", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray rnn(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:rnn", {a.handle()}, kw);
}
inline NDArray rnn(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:rnn", {a.handle(), b.handle()}, kw);
}
inline NDArray rnn(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:rnn", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray roi_align(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:roi_align", {a.handle()}, kw);
}
inline NDArray roi_align(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:roi_align", {a.handle(), b.handle()}, kw);
}
inline NDArray roi_align(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:roi_align", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray roi_pooling(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:roi_pooling", {a.handle()}, kw);
}
inline NDArray roi_pooling(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:roi_pooling", {a.handle(), b.handle()}, kw);
}
inline NDArray roi_pooling(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:roi_pooling", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray rroi_align(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:rroi_align", {a.handle()}, kw);
}
inline NDArray rroi_align(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:rroi_align", {a.handle(), b.handle()}, kw);
}
inline NDArray rroi_align(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:rroi_align", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray rsqrt(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:rsqrt", {a.handle()}, kw);
}
inline NDArray rsqrt(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:rsqrt", {a.handle(), b.handle()}, kw);
}
inline NDArray rsqrt(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:rsqrt", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray scatter_nd(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:scatter_nd", {a.handle()}, kw);
}
inline NDArray scatter_nd(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:scatter_nd", {a.handle(), b.handle()}, kw);
}
inline NDArray scatter_nd(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:scatter_nd", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray sequence_last(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sequence_last", {a.handle()}, kw);
}
inline NDArray sequence_last(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sequence_last", {a.handle(), b.handle()}, kw);
}
inline NDArray sequence_last(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sequence_last", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray sequence_mask(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sequence_mask", {a.handle()}, kw);
}
inline NDArray sequence_mask(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sequence_mask", {a.handle(), b.handle()}, kw);
}
inline NDArray sequence_mask(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sequence_mask", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray sequence_reverse(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sequence_reverse", {a.handle()}, kw);
}
inline NDArray sequence_reverse(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sequence_reverse", {a.handle(), b.handle()}, kw);
}
inline NDArray sequence_reverse(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sequence_reverse", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray set_np(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:set_np", {a.handle()}, kw);
}
inline NDArray set_np(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:set_np", {a.handle(), b.handle()}, kw);
}
inline NDArray set_np(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:set_np", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray shape_array(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:shape_array", {a.handle()}, kw);
}
inline NDArray shape_array(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:shape_array", {a.handle(), b.handle()}, kw);
}
inline NDArray shape_array(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:shape_array", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray sigmoid(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sigmoid", {a.handle()}, kw);
}
inline NDArray sigmoid(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sigmoid", {a.handle(), b.handle()}, kw);
}
inline NDArray sigmoid(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sigmoid", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray silu(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:silu", {a.handle()}, kw);
}
inline NDArray silu(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:silu", {a.handle(), b.handle()}, kw);
}
inline NDArray silu(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:silu", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray sldwin_atten_context(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sldwin_atten_context", {a.handle()}, kw);
}
inline NDArray sldwin_atten_context(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sldwin_atten_context", {a.handle(), b.handle()}, kw);
}
inline NDArray sldwin_atten_context(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sldwin_atten_context", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray sldwin_atten_mask_like(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sldwin_atten_mask_like", {a.handle()}, kw);
}
inline NDArray sldwin_atten_mask_like(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sldwin_atten_mask_like", {a.handle(), b.handle()}, kw);
}
inline NDArray sldwin_atten_mask_like(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sldwin_atten_mask_like", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray sldwin_atten_score(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sldwin_atten_score", {a.handle()}, kw);
}
inline NDArray sldwin_atten_score(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sldwin_atten_score", {a.handle(), b.handle()}, kw);
}
inline NDArray sldwin_atten_score(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:sldwin_atten_score", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray slice(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:slice", {a.handle()}, kw);
}
inline NDArray slice(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:slice", {a.handle(), b.handle()}, kw);
}
inline NDArray slice(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:slice", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray slice_axis(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:slice_axis", {a.handle()}, kw);
}
inline NDArray slice_axis(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:slice_axis", {a.handle(), b.handle()}, kw);
}
inline NDArray slice_axis(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:slice_axis", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray slice_like(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:slice_like", {a.handle()}, kw);
}
inline NDArray slice_like(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:slice_like", {a.handle(), b.handle()}, kw);
}
inline NDArray slice_like(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:slice_like", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray smooth_l1(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:smooth_l1", {a.handle()}, kw);
}
inline NDArray smooth_l1(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:smooth_l1", {a.handle(), b.handle()}, kw);
}
inline NDArray smooth_l1(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:smooth_l1", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray softmax(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:softmax", {a.handle()}, kw);
}
inline NDArray softmax(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:softmax", {a.handle(), b.handle()}, kw);
}
inline NDArray softmax(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:softmax", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray softmax_output(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:softmax_output", {a.handle()}, kw);
}
inline NDArray softmax_output(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:softmax_output", {a.handle(), b.handle()}, kw);
}
inline NDArray softmax_output(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:softmax_output", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray softmin(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:softmin", {a.handle()}, kw);
}
inline NDArray softmin(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:softmin", {a.handle(), b.handle()}, kw);
}
inline NDArray softmin(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:softmin", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray softplus(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:softplus", {a.handle()}, kw);
}
inline NDArray softplus(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:softplus", {a.handle(), b.handle()}, kw);
}
inline NDArray softplus(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:softplus", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray softsign(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:softsign", {a.handle()}, kw);
}
inline NDArray softsign(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:softsign", {a.handle(), b.handle()}, kw);
}
inline NDArray softsign(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:softsign", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray space_to_depth(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:space_to_depth", {a.handle()}, kw);
}
inline NDArray space_to_depth(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:space_to_depth", {a.handle(), b.handle()}, kw);
}
inline NDArray space_to_depth(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:space_to_depth", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray spatial_transformer(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:spatial_transformer", {a.handle()}, kw);
}
inline NDArray spatial_transformer(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:spatial_transformer", {a.handle(), b.handle()}, kw);
}
inline NDArray spatial_transformer(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:spatial_transformer", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray stop_gradient(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:stop_gradient", {a.handle()}, kw);
}
inline NDArray stop_gradient(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:stop_gradient", {a.handle(), b.handle()}, kw);
}
inline NDArray stop_gradient(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:stop_gradient", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray topk(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:topk", {a.handle()}, kw);
}
inline NDArray topk(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:topk", {a.handle(), b.handle()}, kw);
}
inline NDArray topk(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:topk", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray upsampling(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:upsampling", {a.handle()}, kw);
}
inline NDArray upsampling(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:upsampling", {a.handle(), b.handle()}, kw);
}
inline NDArray upsampling(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:upsampling", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray waitall(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:waitall", {a.handle()}, kw);
}
inline NDArray waitall(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:waitall", {a.handle(), b.handle()}, kw);
}
inline NDArray waitall(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:waitall", {a.handle(), b.handle(), c.handle()}, kw);
}

inline NDArray while_loop(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("npx:while_loop", {a.handle()}, kw);
}
inline NDArray while_loop(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("npx:while_loop", {a.handle(), b.handle()}, kw);
}
inline NDArray while_loop(const NDArray& a, const NDArray& b,
                    const NDArray& c,
                    const std::string& kw = "") {
  return detail::Invoke("npx:while_loop", {a.handle(), b.handle(), c.handle()}, kw);
}

namespace linalg {

inline NDArray apply_op(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:apply_op", {a.handle()}, kw);
}
inline NDArray apply_op(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:apply_op", {a.handle(), b.handle()}, kw);
}

inline NDArray cholesky(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:cholesky", {a.handle()}, kw);
}
inline NDArray cholesky(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:cholesky", {a.handle(), b.handle()}, kw);
}

inline NDArray cond(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:cond", {a.handle()}, kw);
}
inline NDArray cond(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:cond", {a.handle(), b.handle()}, kw);
}

inline NDArray det(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:det", {a.handle()}, kw);
}
inline NDArray det(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:det", {a.handle(), b.handle()}, kw);
}

inline NDArray eig(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:eig", {a.handle()}, kw);
}
inline NDArray eig(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:eig", {a.handle(), b.handle()}, kw);
}

inline NDArray eigh(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:eigh", {a.handle()}, kw);
}
inline NDArray eigh(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:eigh", {a.handle(), b.handle()}, kw);
}

inline NDArray eigvals(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:eigvals", {a.handle()}, kw);
}
inline NDArray eigvals(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:eigvals", {a.handle(), b.handle()}, kw);
}

inline NDArray eigvalsh(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:eigvalsh", {a.handle()}, kw);
}
inline NDArray eigvalsh(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:eigvalsh", {a.handle(), b.handle()}, kw);
}

inline NDArray inv(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:inv", {a.handle()}, kw);
}
inline NDArray inv(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:inv", {a.handle(), b.handle()}, kw);
}

inline NDArray lstsq(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:lstsq", {a.handle()}, kw);
}
inline NDArray lstsq(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:lstsq", {a.handle(), b.handle()}, kw);
}

inline NDArray matrix_power(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:matrix_power", {a.handle()}, kw);
}
inline NDArray matrix_power(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:matrix_power", {a.handle(), b.handle()}, kw);
}

inline NDArray matrix_rank(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:matrix_rank", {a.handle()}, kw);
}
inline NDArray matrix_rank(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:matrix_rank", {a.handle(), b.handle()}, kw);
}

inline NDArray multi_dot(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:multi_dot", {a.handle()}, kw);
}
inline NDArray multi_dot(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:multi_dot", {a.handle(), b.handle()}, kw);
}

inline NDArray norm(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:norm", {a.handle()}, kw);
}
inline NDArray norm(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:norm", {a.handle(), b.handle()}, kw);
}

inline NDArray pinv(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:pinv", {a.handle()}, kw);
}
inline NDArray pinv(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:pinv", {a.handle(), b.handle()}, kw);
}

inline NDArray qr(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:qr", {a.handle()}, kw);
}
inline NDArray qr(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:qr", {a.handle(), b.handle()}, kw);
}

inline NDArray slogdet(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:slogdet", {a.handle()}, kw);
}
inline NDArray slogdet(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:slogdet", {a.handle(), b.handle()}, kw);
}

inline NDArray solve(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:solve", {a.handle()}, kw);
}
inline NDArray solve(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:solve", {a.handle(), b.handle()}, kw);
}

inline NDArray svd(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:svd", {a.handle()}, kw);
}
inline NDArray svd(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:svd", {a.handle(), b.handle()}, kw);
}

inline NDArray tensorinv(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:tensorinv", {a.handle()}, kw);
}
inline NDArray tensorinv(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:tensorinv", {a.handle(), b.handle()}, kw);
}

inline NDArray tensorsolve(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:tensorsolve", {a.handle()}, kw);
}
inline NDArray tensorsolve(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("linalg:tensorsolve", {a.handle(), b.handle()}, kw);
}

}  // namespace linalg

namespace random {

inline NDArray bernoulli(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:bernoulli", {a.handle()}, kw);
}
inline NDArray bernoulli(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:bernoulli", {a.handle(), b.handle()}, kw);
}

inline NDArray beta(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:beta", {a.handle()}, kw);
}
inline NDArray beta(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:beta", {a.handle(), b.handle()}, kw);
}

inline NDArray binomial(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:binomial", {a.handle()}, kw);
}
inline NDArray binomial(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:binomial", {a.handle(), b.handle()}, kw);
}

inline NDArray chisquare(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:chisquare", {a.handle()}, kw);
}
inline NDArray chisquare(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:chisquare", {a.handle(), b.handle()}, kw);
}

inline NDArray choice(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:choice", {a.handle()}, kw);
}
inline NDArray choice(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:choice", {a.handle(), b.handle()}, kw);
}

inline NDArray current_context(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:current_context", {a.handle()}, kw);
}
inline NDArray current_context(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:current_context", {a.handle(), b.handle()}, kw);
}

inline NDArray exponential(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:exponential", {a.handle()}, kw);
}
inline NDArray exponential(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:exponential", {a.handle(), b.handle()}, kw);
}

inline NDArray f(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:f", {a.handle()}, kw);
}
inline NDArray f(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:f", {a.handle(), b.handle()}, kw);
}

inline NDArray gamma(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:gamma", {a.handle()}, kw);
}
inline NDArray gamma(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:gamma", {a.handle(), b.handle()}, kw);
}

inline NDArray geometric(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:geometric", {a.handle()}, kw);
}
inline NDArray geometric(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:geometric", {a.handle(), b.handle()}, kw);
}

inline NDArray gumbel(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:gumbel", {a.handle()}, kw);
}
inline NDArray gumbel(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:gumbel", {a.handle(), b.handle()}, kw);
}

inline NDArray laplace(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:laplace", {a.handle()}, kw);
}
inline NDArray laplace(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:laplace", {a.handle(), b.handle()}, kw);
}

inline NDArray logistic(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:logistic", {a.handle()}, kw);
}
inline NDArray logistic(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:logistic", {a.handle(), b.handle()}, kw);
}

inline NDArray lognormal(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:lognormal", {a.handle()}, kw);
}
inline NDArray lognormal(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:lognormal", {a.handle(), b.handle()}, kw);
}

inline NDArray multinomial(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:multinomial", {a.handle()}, kw);
}
inline NDArray multinomial(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:multinomial", {a.handle(), b.handle()}, kw);
}

inline NDArray multivariate_normal(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:multivariate_normal", {a.handle()}, kw);
}
inline NDArray multivariate_normal(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:multivariate_normal", {a.handle(), b.handle()}, kw);
}

inline NDArray negative_binomial(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:negative_binomial", {a.handle()}, kw);
}
inline NDArray negative_binomial(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:negative_binomial", {a.handle(), b.handle()}, kw);
}

inline NDArray next_key(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:next_key", {a.handle()}, kw);
}
inline NDArray next_key(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:next_key", {a.handle(), b.handle()}, kw);
}

inline NDArray normal(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:normal", {a.handle()}, kw);
}
inline NDArray normal(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:normal", {a.handle(), b.handle()}, kw);
}

inline NDArray pareto(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:pareto", {a.handle()}, kw);
}
inline NDArray pareto(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:pareto", {a.handle(), b.handle()}, kw);
}

inline NDArray permutation(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:permutation", {a.handle()}, kw);
}
inline NDArray permutation(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:permutation", {a.handle(), b.handle()}, kw);
}

inline NDArray poisson(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:poisson", {a.handle()}, kw);
}
inline NDArray poisson(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:poisson", {a.handle(), b.handle()}, kw);
}

inline NDArray power(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:power", {a.handle()}, kw);
}
inline NDArray power(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:power", {a.handle(), b.handle()}, kw);
}

inline NDArray rand(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:rand", {a.handle()}, kw);
}
inline NDArray rand(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:rand", {a.handle(), b.handle()}, kw);
}

inline NDArray randint(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:randint", {a.handle()}, kw);
}
inline NDArray randint(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:randint", {a.handle(), b.handle()}, kw);
}

inline NDArray randn(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:randn", {a.handle()}, kw);
}
inline NDArray randn(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:randn", {a.handle(), b.handle()}, kw);
}

inline NDArray rayleigh(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:rayleigh", {a.handle()}, kw);
}
inline NDArray rayleigh(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:rayleigh", {a.handle(), b.handle()}, kw);
}

inline NDArray resolve_dtype(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:resolve_dtype", {a.handle()}, kw);
}
inline NDArray resolve_dtype(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:resolve_dtype", {a.handle(), b.handle()}, kw);
}

inline NDArray seed(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:seed", {a.handle()}, kw);
}
inline NDArray seed(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:seed", {a.handle(), b.handle()}, kw);
}

inline NDArray shuffle(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:shuffle", {a.handle()}, kw);
}
inline NDArray shuffle(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:shuffle", {a.handle(), b.handle()}, kw);
}

inline NDArray uniform(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:uniform", {a.handle()}, kw);
}
inline NDArray uniform(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:uniform", {a.handle(), b.handle()}, kw);
}

inline NDArray weibull(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("random:weibull", {a.handle()}, kw);
}
inline NDArray weibull(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("random:weibull", {a.handle(), b.handle()}, kw);
}

}  // namespace random

namespace fft {

inline NDArray apply_op(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:apply_op", {a.handle()}, kw);
}
inline NDArray apply_op(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:apply_op", {a.handle(), b.handle()}, kw);
}

inline NDArray fft(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:fft", {a.handle()}, kw);
}
inline NDArray fft(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:fft", {a.handle(), b.handle()}, kw);
}

inline NDArray fft2(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:fft2", {a.handle()}, kw);
}
inline NDArray fft2(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:fft2", {a.handle(), b.handle()}, kw);
}

inline NDArray fftfreq(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:fftfreq", {a.handle()}, kw);
}
inline NDArray fftfreq(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:fftfreq", {a.handle(), b.handle()}, kw);
}

inline NDArray fftn(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:fftn", {a.handle()}, kw);
}
inline NDArray fftn(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:fftn", {a.handle(), b.handle()}, kw);
}

inline NDArray fftshift(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:fftshift", {a.handle()}, kw);
}
inline NDArray fftshift(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:fftshift", {a.handle(), b.handle()}, kw);
}

inline NDArray hfft(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:hfft", {a.handle()}, kw);
}
inline NDArray hfft(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:hfft", {a.handle(), b.handle()}, kw);
}

inline NDArray ifft(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:ifft", {a.handle()}, kw);
}
inline NDArray ifft(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:ifft", {a.handle(), b.handle()}, kw);
}

inline NDArray ifft2(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:ifft2", {a.handle()}, kw);
}
inline NDArray ifft2(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:ifft2", {a.handle(), b.handle()}, kw);
}

inline NDArray ifftn(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:ifftn", {a.handle()}, kw);
}
inline NDArray ifftn(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:ifftn", {a.handle(), b.handle()}, kw);
}

inline NDArray ifftshift(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:ifftshift", {a.handle()}, kw);
}
inline NDArray ifftshift(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:ifftshift", {a.handle(), b.handle()}, kw);
}

inline NDArray ihfft(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:ihfft", {a.handle()}, kw);
}
inline NDArray ihfft(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:ihfft", {a.handle(), b.handle()}, kw);
}

inline NDArray irfft(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:irfft", {a.handle()}, kw);
}
inline NDArray irfft(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:irfft", {a.handle(), b.handle()}, kw);
}

inline NDArray irfft2(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:irfft2", {a.handle()}, kw);
}
inline NDArray irfft2(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:irfft2", {a.handle(), b.handle()}, kw);
}

inline NDArray irfftn(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:irfftn", {a.handle()}, kw);
}
inline NDArray irfftn(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:irfftn", {a.handle(), b.handle()}, kw);
}

inline NDArray rfft(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:rfft", {a.handle()}, kw);
}
inline NDArray rfft(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:rfft", {a.handle(), b.handle()}, kw);
}

inline NDArray rfft2(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:rfft2", {a.handle()}, kw);
}
inline NDArray rfft2(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:rfft2", {a.handle(), b.handle()}, kw);
}

inline NDArray rfftfreq(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:rfftfreq", {a.handle()}, kw);
}
inline NDArray rfftfreq(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:rfftfreq", {a.handle(), b.handle()}, kw);
}

inline NDArray rfftn(const NDArray& a,
                    const std::string& kw = "") {
  return detail::Invoke("fft:rfftn", {a.handle()}, kw);
}
inline NDArray rfftn(const NDArray& a, const NDArray& b,
                    const std::string& kw = "") {
  return detail::Invoke("fft:rfftn", {a.handle(), b.handle()}, kw);
}

}  // namespace fft

}  // namespace ops
}  // namespace mxtpu

#endif  // MXTPU_OPS_HPP_
