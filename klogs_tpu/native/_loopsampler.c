/* _loopsampler — the clock of the event-loop sampler (obs/loopprof.py).
 *
 * A thread that never takes the GIL wakes hz times a second and queues
 * a pending call for the main thread. The interpreter runs it on the
 * main thread (the collector's event loop) at its next bytecode
 * boundary, where the current frame is the one the loop was executing:
 * a sample lands where the loop is at that instant. A sampler that
 * had to take the GIL first could only read the loop where the loop
 * next released it (a system call, a large copy), not where it spends
 * its time. No signal is sent, so no system call is interrupted.
 *
 * Ticks count up while the main thread cannot run the call (in a
 * system call, in a long C call, or waiting for the GIL); the call
 * hands them all to the Python callback, for the frame the loop comes
 * back to, one call in flight at a time.
 *
 * Python 3.12 queues a call for the main thread from another thread
 * without waking its eval loop (the eval breaker is recomputed in the
 * calling thread, which cannot run it), so the thread sets the
 * breaker itself, as the interpreter does for a signal it must force
 * through; the main thread recomputes it when it runs the call. Later
 * versions wake the main thread themselves. This needs the
 * interpreter's internal layout, hence a module of its own.
 *
 *   start(callback, hz): callback(frame, ticks) from the main thread
 *   stop(): end and join the thread; a call already queued is dropped
 */

#define PY_SSIZE_T_CLEAN
#define Py_BUILD_CORE 1
#include <Python.h>
#if PY_VERSION_HEX < 0x030D0000
#include "internal/pycore_interp.h"
#endif
#include <errno.h>
#include <pthread.h>
#include <stdatomic.h>
#include <time.h>

static PyObject *ls_callback = NULL;  /* GIL-guarded */
static pthread_t ls_thread;
static int ls_running = 0;            /* GIL-guarded */
static long ls_period_ns = 0;
static PyInterpreterState *ls_interp = NULL;
static atomic_int ls_stop;
static atomic_int ls_in_flight;
static atomic_long ls_owed;

/* Runs on the main thread with the GIL; must never raise into the code
 * it interrupted. */
static int
ls_pending(void *arg)
{
    (void)arg;
    atomic_store(&ls_in_flight, 0);
    long n = atomic_exchange(&ls_owed, 0);
    if (n <= 0 || ls_callback == NULL)
        return 0;
    PyObject *frame = (PyObject *)PyEval_GetFrame();
    PyObject *res = PyObject_CallFunction(ls_callback, "Ol",
                                          frame ? frame : Py_None, n);
    if (res == NULL)
        PyErr_WriteUnraisable(ls_callback);
    else
        Py_DECREF(res);
    return 0;
}

static void *
ls_main(void *arg)
{
    (void)arg;
    struct timespec next, now;
    clock_gettime(CLOCK_MONOTONIC, &next);
    while (!atomic_load(&ls_stop)) {
        next.tv_nsec += ls_period_ns;
        while (next.tv_nsec >= 1000000000L) {
            next.tv_nsec -= 1000000000L;
            next.tv_sec += 1;
        }
        clock_gettime(CLOCK_MONOTONIC, &now);
        if (now.tv_sec > next.tv_sec + 1)
            next = now;  /* the process was stopped: no burst of ticks */
        while (clock_nanosleep(CLOCK_MONOTONIC, TIMER_ABSTIME, &next,
                               NULL) == EINTR)
            ;
        if (atomic_load(&ls_stop))
            break;
        atomic_fetch_add(&ls_owed, 1);
        if (!atomic_exchange(&ls_in_flight, 1)
                && Py_AddPendingCall(ls_pending, NULL) != 0) {
            atomic_store(&ls_in_flight, 0);  /* queue full: next tick */
            continue;
        }
#if PY_VERSION_HEX < 0x030D0000
        /* Every tick while the call waits: another thread that checks
         * the breaker recomputes it without the main thread's calls. */
        _Py_atomic_store_relaxed(&ls_interp->ceval.eval_breaker, 1);
#endif
    }
    return NULL;
}

static PyObject *
ls_start(PyObject *self, PyObject *args)
{
    PyObject *callback;
    double hz;
    (void)self;
    if (!PyArg_ParseTuple(args, "Od", &callback, &hz))
        return NULL;
    if (ls_running) {
        PyErr_SetString(PyExc_RuntimeError, "loop sampler already running");
        return NULL;
    }
    if (!PyCallable_Check(callback) || hz <= 0.0 || hz > 10000.0) {
        PyErr_SetString(PyExc_ValueError,
                        "loop sampler: a callable and 0 < hz <= 10000");
        return NULL;
    }
    ls_interp = PyInterpreterState_Main();
    ls_period_ns = (long)(1e9 / hz);
    atomic_store(&ls_stop, 0);
    atomic_store(&ls_in_flight, 0);
    atomic_store(&ls_owed, 0);
    Py_INCREF(callback);
    Py_XSETREF(ls_callback, callback);
    if (pthread_create(&ls_thread, NULL, ls_main, NULL) != 0) {
        Py_CLEAR(ls_callback);
        PyErr_SetString(PyExc_OSError, "loop sampler: pthread_create failed");
        return NULL;
    }
    ls_running = 1;
    Py_RETURN_NONE;
}

static PyObject *
ls_stop_(PyObject *self, PyObject *args)
{
    (void)self;
    (void)args;
    if (ls_running) {
        atomic_store(&ls_stop, 1);
        Py_BEGIN_ALLOW_THREADS
        pthread_join(ls_thread, NULL);
        Py_END_ALLOW_THREADS
        ls_running = 0;
        Py_CLEAR(ls_callback);
    }
    Py_RETURN_NONE;
}

static PyMethodDef Methods[] = {
    {"start", ls_start, METH_VARARGS,
     "start(callback, hz): call callback(frame, ticks) on the main thread"
     " at its next bytecode boundary, hz times a second"},
    {"stop", ls_stop_, METH_NOARGS,
     "stop(): end and join the sampler thread"},
    {NULL, NULL, 0, NULL},
};

static struct PyModuleDef module = {
    PyModuleDef_HEAD_INIT, "_loopsampler",
    "Event-loop sampler clock for klogs_tpu", -1, Methods,
};

PyMODINIT_FUNC
PyInit__loopsampler(void)
{
    return PyModule_Create(&module);
}
