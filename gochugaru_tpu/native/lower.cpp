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
// gl_contexts groups the batch's request contexts (each caveat_context)
// as caveats/device.py dedup_contexts groups them: rows that hold equal
// values of equal types in every declared parameter share a group, groups
// ranked in the order of their first row.  It declines a batch (returns
// None) where dedup_contexts would key a column by repr or a context is
// not an exact dict, and the caller runs the Python pass over the whole
// batch, so a batch is grouped by one pass or the other, never by both.
//
// Kept apart from ingest.cpp: it needs Python.h, and a host without the
// interpreter's headers loses only this pass, never the interner.

#define PY_SSIZE_T_CLEAN
#include <Python.h>

#include <algorithm>
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

PyObject* g_context = nullptr;  // "caveat_context", interned

// the value of one parameter in a row: nullptr where the context does not
// name it, else None, False, True or an exact str or int.  Two values
// encode alike exactly where they are the same object or of one type and
// equal (1 and True do not), as dedup_contexts keys them
bool same_value(PyObject* a, PyObject* b) {
  if (a == b) return true;
  if (a == nullptr || b == nullptr || Py_TYPE(a) != Py_TYPE(b)) return false;
  if (PyUnicode_CheckExact(a)) return same_str(a, b);
  // two exact ints (None, False and True are singletons): the comparison
  // runs no Python code and cannot fail
  return PyLong_CheckExact(a) && PyObject_RichCompareBool(a, b, Py_EQ) == 1;
}

uint64_t mix(uint64_t x) {  // splitmix64's finaliser
  x = (x ^ (x >> 30)) * 0xbf58476d1ce4e5b9ULL;
  x = (x ^ (x >> 27)) * 0x94d049bb133111ebULL;
  return x ^ (x >> 31);
}

// The groups of a batch: an open-addressing table of a row's combined hash
// -> group, and each group's P values (strong references), taken from its
// first row.  Groups are numbered in the order they first come.
class Groups {
 public:
  explicit Groups(size_t P) : P_(P) {}
  ~Groups() {
    for (PyObject* v : values_) Py_XDECREF(v);
  }

  size_t size() const { return count_; }

  // the group of the row whose values are row[P] and hash h; a new group
  // takes new references to them
  int32_t find(const PyObject* const* row, uint64_t h) {
    if (2 * (count_ + 1) > slots_.size()) grow();
    const size_t mask = slots_.size() - 1;
    for (size_t i = h & mask;; i = (i + 1) & mask) {
      Slot& s = slots_[i];
      if (s.group < 0) {
        s = {h, static_cast<int32_t>(count_++)};
        for (size_t p = 0; p < P_; ++p) {
          PyObject* v = const_cast<PyObject*>(row[p]);
          Py_XINCREF(v);
          values_.push_back(v);
        }
        return s.group;
      }
      if (s.hash == h && same_row(s.group, row)) return s.group;
    }
  }

 private:
  struct Slot {
    uint64_t hash;
    int32_t group;
  };

  bool same_row(int32_t g, const PyObject* const* row) const {
    const PyObject* const* have = &values_[static_cast<size_t>(g) * P_];
    for (size_t p = 0; p < P_; ++p) {
      if (!same_value(const_cast<PyObject*>(have[p]),
                      const_cast<PyObject*>(row[p]))) {
        return false;
      }
    }
    return true;
  }

  void grow() {
    std::vector<Slot> old(std::max<size_t>(64, 2 * slots_.size()), Slot{0, -1});
    old.swap(slots_);
    const size_t mask = slots_.size() - 1;
    for (const Slot& s : old) {
      if (s.group < 0) continue;
      size_t i = s.hash & mask;
      while (slots_[i].group >= 0) i = (i + 1) & mask;
      slots_[i] = s;
    }
  }

  size_t P_;
  size_t count_ = 0;
  std::vector<Slot> slots_;
  std::vector<PyObject*> values_;  // group-major, P a group
};

enum class Row { kOk, kDecline, kError };

// one non-empty context's group, or kDecline where dedup_contexts keys a
// parameter of it by repr, or might read it otherwise than this pass.
// The context's entries are walked once, each key matched to a parameter
// as an equal exact str (the names are interned, so a literal key matches
// by address), and no Python code runs; a key of another type might equal
// a name, and a context holding one is declined
Row group_of(PyObject* ctx, PyObject* params, Groups& groups,
             std::vector<PyObject*>& row, std::vector<char>& named,
             int32_t* group) {
  const Py_ssize_t P = PyTuple_GET_SIZE(params);
  PyObject* const* names = PySequence_Fast_ITEMS(params);
  std::fill(row.begin(), row.end(), nullptr);
  Py_ssize_t pos = 0;
  PyObject *k, *v;
  while (PyDict_Next(ctx, &pos, &k, &v)) {
    if (!PyUnicode_CheckExact(k)) return Row::kDecline;
    Py_ssize_t p = 0;
    while (p < P && !same_str(names[p], k)) ++p;
    if (p == P) continue;  // a key no caveat declares
    if (!(v == Py_None || v == Py_False || v == Py_True ||
          PyUnicode_CheckExact(v) || PyLong_CheckExact(v))) {
      return Row::kDecline;
    }
    row[p] = v;
    named[p] = 1;
  }
  uint64_t h = 0;
  for (Py_ssize_t p = 0; p < P; ++p) {
    PyObject* x = row[p];
    uint64_t vh = reinterpret_cast<uintptr_t>(x);  // absent, None, False, True
    if (x != nullptr && (PyUnicode_CheckExact(x) || PyLong_CheckExact(x))) {
      // a str's hash is cached; an exact type's hash never fails
      vh = static_cast<uint64_t>(PyObject_Hash(x)) ^
           reinterpret_cast<uintptr_t>(Py_TYPE(x));
    }
    h = mix(h ^ vh) + static_cast<uint64_t>(p);
  }
  *group = groups.find(row.data(), h);
  return Row::kOk;
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

// Group one batch's request contexts.  rels: a sequence of n objects with
// a caveat_context; params: a tuple of the plan's parameter names.  Fills
// index[n] (-1 where the context is empty, else the row's group, groups
// ranked by their first row) and *keyed (the parameters some context
// names), and returns the list of each group's first context.  Returns
// None, index undefined, where a context is not an exact dict, holds a key
// that is not an exact str, or a parameter holds a value outside str, int,
// bool and None (exact types): the caller groups that batch in Python.
// NULL with the exception set where a row has no caveat_context
// (AttributeError).
PyObject* gl_contexts(PyObject* rels, int64_t n, PyObject* params,
                      int32_t* index, int64_t* keyed) {
  if (!PyTuple_CheckExact(params)) {
    PyErr_SetString(PyExc_TypeError, "gl_contexts: params must be a tuple");
    return nullptr;
  }
  if (g_context == nullptr) {
    g_context = PyUnicode_InternFromString("caveat_context");
    if (g_context == nullptr) return nullptr;
  }
  PyObject* seq = PySequence_Fast(rels, "gl_contexts: rels must be a sequence");
  if (seq == nullptr) return nullptr;
  const Py_ssize_t B = PySequence_Fast_GET_SIZE(seq);
  PyObject* rows = B == n ? PyList_New(0) : nullptr;
  if (B != n) {
    PyErr_SetString(PyExc_ValueError, "gl_contexts: rels is not n long");
  }
  const size_t P = static_cast<size_t>(PyTuple_GET_SIZE(params));
  Groups groups(P);
  std::vector<PyObject*> row(P, nullptr);
  std::vector<char> named(P, 0);
  Row state = rows == nullptr ? Row::kError : Row::kOk;
  for (Py_ssize_t i = 0; state == Row::kOk && i < B; ++i) {
    // a getter that runs Python code may resize a list under the pass
    if (PySequence_Fast_GET_SIZE(seq) != B) {
      PyErr_SetString(PyExc_RuntimeError,
                      "gl_contexts: rels changed size during the pass");
      state = Row::kError;
      break;
    }
    PyObject* r = PySequence_Fast_GET_ITEM(seq, i);
    Py_INCREF(r);
    PyObject* ctx = PyObject_GetAttr(r, g_context);
    Py_DECREF(r);
    if (ctx == nullptr) {
      state = Row::kError;
    } else if (!PyDict_CheckExact(ctx)) {
      state = Row::kDecline;
    } else if (PyDict_GET_SIZE(ctx) == 0) {
      index[i] = -1;
    } else {
      const size_t known = groups.size();
      state = group_of(ctx, params, groups, row, named, &index[i]);
      if (state == Row::kOk && groups.size() > known &&
          PyList_Append(rows, ctx) < 0) {
        state = Row::kError;
      }
    }
    Py_XDECREF(ctx);
  }
  Py_DECREF(seq);
  if (state != Row::kOk) {
    Py_XDECREF(rows);
    if (state == Row::kError) return nullptr;
    Py_RETURN_NONE;
  }
  *keyed = 0;
  for (char c : named) *keyed += c;
  return rows;
}

}  // extern "C"
