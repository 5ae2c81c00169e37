/* frontcore: the HTTP front's socket calls, a batch per GIL release.
 *
 * The event-loop front (etcd_tpu/etcdhttp/web.py) makes one recv and one
 * send per request. Each is cheap as a system call (13 / 22 us on the
 * chip's gVisor host) and dear as a Python call: socket.recv / send
 * release the interpreter lock around the call, the engine's round
 * thread or an applier takes it, and the loop waits for it again, 120 us
 * and more each time, twice a request (PERF.md, PR 25). So the loop
 * gathers the sockets that are readable, and the replies that are ready,
 * and hands each set over in ONE call:
 *
 *   recv_many(fds, bufsize) -> [bytes | -errno, ...]
 *       One non-blocking recv of up to `bufsize` bytes per descriptor
 *       (less when many descriptors share the call's 4 MiB of scratch,
 *       never under 4 KiB: more than 1024 descriptors are worked off
 *       1024 at a time inside the call, any number of them).
 *       b"" is end of file; a negative int is -errno (EAGAIN: nothing
 *       to read after all).
 *
 *   send_many([(fd, data), ...]) -> [sent | -errno, ...]
 *       One non-blocking send per item (MSG_NOSIGNAL); `data` is any
 *       contiguous buffer (bytes, bytearray). The caller keeps what was
 *       not taken.
 *
 * Both release the interpreter lock once, around the whole batch (recv_many
 * once per 1024 descriptors): the system calls of a cohort run while the
 * engine's threads run Python.
 * The pure-Python fallbacks (etcd_tpu/native/__init__.py: os.read /
 * os.write in a loop) return the same lists; tests/test_native.py holds
 * the two to each other. Built by ./build; loading is optional.
 */
#define PY_SSIZE_T_CLEAN
#include <Python.h>
#include <errno.h>
#include <stdlib.h>
#include <sys/socket.h>
#include <sys/types.h>

#define SCRATCH_MAX (4 * 1024 * 1024)
#define MIN_CHUNK 4096
#define GROUP (SCRATCH_MAX / MIN_CHUNK)

static PyObject *recv_many(PyObject *self, PyObject *args) {
    PyObject *fds;
    Py_ssize_t bufsize;
    if (!PyArg_ParseTuple(args, "On", &fds, &bufsize)) return NULL;
    PyObject *seq = PySequence_Fast(fds, "fds must be a sequence");
    if (!seq) return NULL;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
    if (bufsize <= 0) {
        Py_DECREF(seq);
        PyErr_SetString(PyExc_ValueError, "bufsize must be positive");
        return NULL;
    }
    if (n == 0) {
        Py_DECREF(seq);
        return PyList_New(0);
    }
    /* At most GROUP descriptors share the scratch at a time, so each
     * gets MIN_CHUNK bytes or more; a longer list is worked off group by
     * group inside this one call, the lock released once a group. */
    Py_ssize_t group = n < GROUP ? n : GROUP;
    size_t chunk = (size_t)bufsize;
    if (chunk * (size_t)group > SCRATCH_MAX)
        chunk = SCRATCH_MAX / (size_t)group;
    int *fdv = malloc(sizeof(int) * (size_t)n);
    ssize_t *res = malloc(sizeof(ssize_t) * (size_t)group);
    char *scratch = malloc(chunk * (size_t)group);
    PyObject *out = NULL;
    if (!fdv || !res || !scratch) {
        PyErr_NoMemory();
        goto done;
    }
    for (Py_ssize_t i = 0; i < n; i++) {
        long fd = PyLong_AsLong(PySequence_Fast_GET_ITEM(seq, i));
        if (fd == -1 && PyErr_Occurred()) goto done;
        fdv[i] = (int)fd;
    }
    out = PyList_New(n);
    if (!out) goto done;
    for (Py_ssize_t base = 0; base < n; base += group) {
        Py_ssize_t m = n - base < group ? n - base : group;
        Py_BEGIN_ALLOW_THREADS
        for (Py_ssize_t i = 0; i < m; i++) {
            ssize_t r;
            do {
                r = recv(fdv[base + i], scratch + chunk * (size_t)i, chunk,
                         MSG_DONTWAIT);
            } while (r < 0 && errno == EINTR);
            res[i] = r >= 0 ? r : -(ssize_t)errno;
        }
        Py_END_ALLOW_THREADS
        for (Py_ssize_t i = 0; i < m; i++) {
            PyObject *item = res[i] >= 0
                ? PyBytes_FromStringAndSize(scratch + chunk * (size_t)i,
                                            res[i])
                : PyLong_FromSsize_t(res[i]);
            if (!item) {
                Py_CLEAR(out);      /* frees the items set so far */
                goto done;
            }
            PyList_SET_ITEM(out, base + i, item);
        }
    }
done:
    free(fdv);
    free(res);
    free(scratch);
    Py_DECREF(seq);
    return out;
}

static PyObject *send_many(PyObject *self, PyObject *args) {
    PyObject *items;
    if (!PyArg_ParseTuple(args, "O", &items)) return NULL;
    PyObject *seq = PySequence_Fast(items, "items must be a sequence");
    if (!seq) return NULL;
    Py_ssize_t n = PySequence_Fast_GET_SIZE(seq);
    if (n == 0) {
        Py_DECREF(seq);
        return PyList_New(0);
    }
    int *fdv = malloc(sizeof(int) * (size_t)n);
    ssize_t *res = malloc(sizeof(ssize_t) * (size_t)n);
    Py_buffer *views = calloc((size_t)n, sizeof(Py_buffer));
    Py_ssize_t held = 0;            /* views[0..held) are to be released */
    PyObject *out = NULL;
    if (!fdv || !res || !views) {
        PyErr_NoMemory();
        goto done;
    }
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *it = PySequence_Fast_GET_ITEM(seq, i);
        if (!PyTuple_Check(it) || PyTuple_GET_SIZE(it) != 2) {
            PyErr_SetString(PyExc_TypeError, "items are (fd, data) tuples");
            goto done;
        }
        long fd = PyLong_AsLong(PyTuple_GET_ITEM(it, 0));
        if (fd == -1 && PyErr_Occurred()) goto done;
        fdv[i] = (int)fd;
        if (PyObject_GetBuffer(PyTuple_GET_ITEM(it, 1), &views[i],
                               PyBUF_SIMPLE) < 0)
            goto done;
        held = i + 1;
    }
    Py_BEGIN_ALLOW_THREADS
    for (Py_ssize_t i = 0; i < n; i++) {
        ssize_t r;
        do {
            r = send(fdv[i], views[i].buf, (size_t)views[i].len,
                     MSG_DONTWAIT | MSG_NOSIGNAL);
        } while (r < 0 && errno == EINTR);
        res[i] = r >= 0 ? r : -(ssize_t)errno;
    }
    Py_END_ALLOW_THREADS
    out = PyList_New(n);
    if (!out) goto done;
    for (Py_ssize_t i = 0; i < n; i++) {
        PyObject *v = PyLong_FromSsize_t(res[i]);
        if (!v) {
            Py_CLEAR(out);
            goto done;
        }
        PyList_SET_ITEM(out, i, v);
    }
done:
    for (Py_ssize_t i = 0; i < held; i++) PyBuffer_Release(&views[i]);
    free(fdv);
    free(res);
    free(views);
    Py_DECREF(seq);
    return out;
}

static PyMethodDef methods[] = {
    {"recv_many", recv_many, METH_VARARGS,
     "recv_many(fds, bufsize:int) -> list[bytes | -errno]"},
    {"send_many", send_many, METH_VARARGS,
     "send_many(list[(fd:int, data)]) -> list[sent | -errno]"},
    {NULL, NULL, 0, NULL}};

static struct PyModuleDef moduledef = {
    PyModuleDef_HEAD_INIT, "frontcore",
    "The HTTP front's socket calls, a batch per GIL release",
    -1, methods};

PyMODINIT_FUNC PyInit_frontcore(void) {
    return PyModule_Create(&moduledef);
}
