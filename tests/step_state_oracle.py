"""The serving tests' check of the device-resident step state
(``InferenceEngine._step_state``; docs/serving.md "The step protocol"):
what the decode program reads of the slots lives on the device and the
program advances it, and the host's mirrors are advanced from the fenced
tokens.  After every ``engine.step()`` the two must agree."""

import threading

import jax
import numpy as np

from horovod_tpu.serve import SamplingParams

def assert_device_is_mirror(engine):
    """The step state read back from the device equals the host's
    mirrors (one locked snapshot of them), field for field."""
    device = jax.device_get(engine._step_state)
    active, positions, temps, topks, tokens = engine._slot_snapshot()[:5]
    mirror = {"tokens": tokens, "positions": positions, "active": active,
              "temps": temps, "topks": topks}
    for field, want in mirror.items():
        np.testing.assert_array_equal(device[field], want, err_msg=field)


def step(engine):
    out = engine.step()
    assert_device_is_mirror(engine)
    return out


def release_mid_flight(engine, slot):
    """One ``engine.step()`` during which another thread releases
    ``slot``, after the step took its snapshot and before its program
    is dispatched (a router's cancel).  The step runs on the snapshot
    it took; its token for ``slot`` is dropped."""
    decode = engine._decode_fn

    def racing(*args):
        t = threading.Thread(target=engine.release, args=(slot,))
        t.start()
        t.join(timeout=30)
        assert not t.is_alive()
        return decode(*args)

    engine._decode_fn = racing
    try:
        out = engine.step()
    finally:
        engine._decode_fn = decode
    assert slot in out and slot in engine.free_slots()
    return out


# What ``drive_lifecycle`` makes: decode steps in all, and those that
# hold its first request alone.
LIFECYCLE_STEPS = 14
LIFECYCLE_STEPS_FIRST_ALONE = 2


def drive_lifecycle(engine, prompts, first=SamplingParams(max_new_tokens=30)):
    """Admissions, steady steps, a finish, a release from another
    thread mid-flight, a preemption and its resume, with the device's
    state compared to the mirrors after every step.  Every request's
    ``(prompt, sampling, tokens)`` comes back, in admission order:
    ``first``'s (greedy unless given), then two that sample."""
    sampling = [first,
                SamplingParams(max_new_tokens=30, temperature=0.9,
                               top_k=20),
                SamplingParams(max_new_tokens=30, temperature=1.2)]
    served = []

    def admit(slot, which):
        rec = (prompts[which], sampling[which],
               [engine.start(slot, prompts[which], sampling[which])])
        served.append(rec)
        return rec

    def steps(n, live):
        for _ in range(n):
            out = step(engine)
            assert sorted(out) == sorted(live)
            for slot, rec in live.items():
                rec[2].extend(out[slot])

    a = admit(0, 0)
    steps(2, {0: a})
    b = admit(1, 1)
    steps(3, {0: a, 1: b})
    engine.release(0)                         # a finish
    steps(2, {1: b})
    c = admit(0, 2)
    steps(2, {0: c, 1: b})
    out = release_mid_flight(engine, 1)       # a cancel, mid-flight
    c[2].extend(out[0])
    steps(2, {0: c})                          # the first re-uploads
    prompt, sp, emitted = c                   # a preemption ...
    rng = engine.preempt_slot(0, prompt, emitted)
    assert engine.active_slots() == []
    engine.resume_slot(1, prompt, emitted, sp, rng)     # ... and resume
    steps(2, {1: c})
    engine.release(1)
    return served
