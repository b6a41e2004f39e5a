// The bulk check lowering's pull over a batch of Relationship objects, in
// one native pass that holds the interpreter lock throughout (loaded with
// ctypes.PyDLL; the Python side is native/lower.py).
//
// gl_pull reads the six key fields of each object, packs the 2*B object
// ids as UTF-8 (resources, then subjects: the layout of
// NativeInterner._pack), and maps the type names and the relation names
// through the dicts it is given, each behind a last-seen memo.  Its
// columns are those of DeviceEngine._lower's Python pass, and so are its
// exception types: a missing field raises AttributeError, a non-str id
// TypeError, an id holding a lone surrogate UnicodeEncodeError, an
// unhashable type or relation TypeError.  A batch with more than one bad
// row may report another of them than the Python pass does.
//
// A field is read with PyObject_GetAttr and an interned name: on a plain
// instance that reads the inline attribute values without building a
// __dict__, and it honours whatever the object's type defines (slots,
// properties, a subclass).
//
// Kept apart from ingest.cpp: it needs Python.h, and a host without the
// interpreter's headers loses only this pass, never the interner.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <cstdint>
#include <cstring>
#include <utility>
#include <vector>

namespace {

enum Field { kResType, kResId, kResRel, kSubjType, kSubjId, kSubjRel };

const char* const kFieldNames[6] = {
    "resource_type", "resource_id", "resource_relation",
    "subject_type", "subject_id", "subject_relation",
};
PyObject* g_fields[6] = {nullptr};

bool intern_fields() {
  for (int j = 0; j < 6; ++j) {
    if (g_fields[j] == nullptr) {
      g_fields[j] = PyUnicode_InternFromString(kFieldNames[j]);
      if (g_fields[j] == nullptr) return false;
    }
  }
  return true;
}

// equal exact str objects: the memo's test, which never runs Python code
bool same_str(PyObject* a, PyObject* b) {
  if (a == b) return true;
  if (!PyUnicode_CheckExact(a) || !PyUnicode_CheckExact(b)) return false;
  const Py_ssize_t n = PyUnicode_GET_LENGTH(a);
  const int kind = PyUnicode_KIND(a);
  return n == PyUnicode_GET_LENGTH(b) && kind == PyUnicode_KIND(b) &&
         std::memcmp(PyUnicode_DATA(a), PyUnicode_DATA(b),
                     static_cast<size_t>(n) * kind) == 0;
}

// dict.get(key, fallback) as int32, remembering the last key and value:
// a column of a batch holds few distinct names, mostly in runs
struct Memo {
  PyObject* dict;
  int32_t fallback;
  PyObject* key = nullptr;  // a strong reference: its address stays its own
  int32_t value = 0;

  Memo(PyObject* d, int32_t f) : dict(d), fallback(f) {}
  ~Memo() { Py_XDECREF(key); }

  bool get(PyObject* k, int32_t* out) {
    if (key != nullptr && same_str(k, key)) {
      *out = value;
      return true;
    }
    PyObject* v = PyDict_GetItemWithError(dict, k);  // borrowed
    long x = fallback;
    if (v != nullptr) {
      x = PyLong_AsLong(v);
      if (x == -1 && PyErr_Occurred()) return false;
    } else if (PyErr_Occurred()) {
      return false;
    }
    Py_INCREF(k);
    Py_XSETREF(key, k);
    value = static_cast<int32_t>(x);
    *out = value;
    return true;
  }
};

// the UTF-8 of one id, or false with the exception set (the messages of
// "".join and str.encode)
bool utf8_of(PyObject* s, Py_ssize_t k, const char** ptr, Py_ssize_t* len) {
  if (!PyUnicode_Check(s)) {
    PyErr_Format(PyExc_TypeError,
                 "sequence item %zd: expected str instance, %.80s found", k,
                 Py_TYPE(s)->tp_name);
    return false;
  }
  *ptr = PyUnicode_AsUTF8AndSize(s, len);
  return *ptr != nullptr;
}

}  // namespace

extern "C" {

// Pull one batch.  rels: a sequence of n objects with the six fields;
// type_ids: type name -> interner type id (-1 when absent); perm_of:
// relation name -> slot (-1 when absent); srel_of: the same with "" -> -1
// (-2 when absent).  Fills offsets[2n + 1] (int64 byte offsets of the
// packed ids), tids[2n] (the ids' type ids), q_perm[n] and q_srel[n], and
// returns the packed ids as bytes, or NULL with the exception set.
PyObject* gl_pull(PyObject* rels, int64_t n, PyObject* type_ids,
                  PyObject* perm_of, PyObject* srel_of, int64_t* offsets,
                  int32_t* tids, int32_t* q_perm, int32_t* q_srel) {
  if (!PyDict_Check(type_ids) || !PyDict_Check(perm_of) ||
      !PyDict_Check(srel_of)) {
    PyErr_SetString(PyExc_TypeError, "gl_pull: the name maps must be dicts");
    return nullptr;
  }
  if (!intern_fields()) return nullptr;
  PyObject* seq = PySequence_Fast(rels, "gl_pull: rels must be a sequence");
  if (seq == nullptr) return nullptr;
  const Py_ssize_t B = PySequence_Fast_GET_SIZE(seq);
  if (B != n) {
    Py_DECREF(seq);
    PyErr_SetString(PyExc_ValueError, "gl_pull: rels is not n long");
    return nullptr;
  }

  // the ids, held until their bytes are copied: a field computed by a
  // property may be the only reference to its string, and the UTF-8
  // pointer lives as long as the string does
  std::vector<PyObject*> ids(static_cast<size_t>(2 * B), nullptr);
  std::vector<const char*> ptr(static_cast<size_t>(2 * B), nullptr);
  std::vector<Py_ssize_t> len(static_cast<size_t>(2 * B), 0);
  bool ok = true;
  {
    Memo res_type(type_ids, -1), subj_type(type_ids, -1);
    Memo perm(perm_of, -1), srel(srel_of, -2);
    for (Py_ssize_t i = 0; ok && i < B; ++i) {
      // a getter that runs Python code may resize a list under the pass
      if (PySequence_Fast_GET_SIZE(seq) != B) {
        PyErr_SetString(PyExc_RuntimeError,
                        "gl_pull: rels changed size during the pull");
        ok = false;
        break;
      }
      PyObject* r = PySequence_Fast_GET_ITEM(seq, i);
      Py_INCREF(r);
      PyObject* f[6] = {nullptr};
      for (int j = 0; ok && j < 6; ++j) {
        f[j] = PyObject_GetAttr(r, g_fields[j]);
        ok = f[j] != nullptr;
      }
      Py_DECREF(r);
      if (ok) {
        std::swap(ids[i], f[kResId]);
        std::swap(ids[B + i], f[kSubjId]);
        ok = utf8_of(ids[i], i, &ptr[i], &len[i]) &&
             utf8_of(ids[B + i], B + i, &ptr[B + i], &len[B + i]) &&
             res_type.get(f[kResType], &tids[i]) &&
             subj_type.get(f[kSubjType], &tids[B + i]) &&
             perm.get(f[kResRel], &q_perm[i]) &&
             srel.get(f[kSubjRel], &q_srel[i]);
      }
      for (int j = 0; j < 6; ++j) Py_XDECREF(f[j]);
    }
  }

  PyObject* out = nullptr;
  if (ok) {
    offsets[0] = 0;
    for (Py_ssize_t k = 0; k < 2 * B; ++k) offsets[k + 1] = offsets[k] + len[k];
    out = PyBytes_FromStringAndSize(nullptr, offsets[2 * B]);
    if (out != nullptr) {
      char* dst = PyBytes_AS_STRING(out);
      for (Py_ssize_t k = 0; k < 2 * B; ++k) {
        std::memcpy(dst + offsets[k], ptr[k], static_cast<size_t>(len[k]));
      }
    }
  }
  for (PyObject* s : ids) Py_XDECREF(s);
  Py_DECREF(seq);
  return out;
}

}  // extern "C"
