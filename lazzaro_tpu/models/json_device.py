"""The JSON grammar automaton ON DEVICE: constrained decode in one dispatch.

``models/json_constrain.py`` runs the pushdown automaton on the host, which
forces one device→host logits round trip PER BYTE of the extraction.
This module is the same grammar as pure jnp scalar ops: mode (an int over
32 states), container stack (fixed [MAX_DEPTH] i8 + depth), and the
string-is-key flag all live on device, so ``LanguageModel.generate_json``
can run its whole sample→mask→feed→decode loop inside ``lax.while_loop``
— ONE dispatch and ONE readback for the entire constrained generation.

Exactness: byte-for-byte the host automaton's semantics (the test suite
replays random legal documents through both and compares masks at every
step), with ONE deliberate restriction — container nesting is capped at
``MAX_DEPTH`` (64): at the cap, '{' and '[' are masked off, so generation
degrades to flat values instead of overflowing the stack. The host
automaton is unbounded; real extraction payloads nest ~3 deep.

Reference analog: none — the reference trusts the remote API's
``response_format`` (providers.py:10-19) and repairs failures by hand
(memory_system.py:684-703).
"""

from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from flax import struct

from lazzaro_tpu.models import json_constrain as host_json

MAX_DEPTH = 64
N_MODES = 32

# Mode encoding. Names mirror json_constrain.JsonState.mode, with the
# force-object-before-first-byte case and each literal suffix given their
# own states so every mask is a pure function of the mode (plus the stack
# top / depth, handled dynamically).
(FVALUE, VALUE, VALUE_OR_CLOSE, OBJ_FIRST, OBJ_KEY, OBJ_COLON, OBJ_AFTER,
 ARR_AFTER, STRING, STR_ESC, STR_U4, STR_U3, STR_U2, STR_U1, NUM_SIGN,
 NUM_ZERO, NUM_INT, NUM_DOT, NUM_FRAC, NUM_E, NUM_ESIGN, NUM_EXP,
 LIT_RUE, LIT_UE, LIT_E, LIT_ALSE, LIT_LSE, LIT_SE, LIT_ULL, LIT_LL,
 LIT_L, DONE) = range(N_MODES)

_NUM_TERMINAL = (NUM_ZERO, NUM_INT, NUM_FRAC, NUM_EXP)

_HOST_MODE = {
    "value": VALUE, "value_or_close": VALUE_OR_CLOSE, "obj_first": OBJ_FIRST,
    "obj_key": OBJ_KEY, "obj_colon": OBJ_COLON, "obj_after": OBJ_AFTER,
    "arr_after": ARR_AFTER, "string": STRING, "string_escape": STR_ESC,
    "string_u4": STR_U4, "string_u3": STR_U3, "string_u2": STR_U2,
    "string_u1": STR_U1, "num_sign": NUM_SIGN, "num_zero": NUM_ZERO,
    "num_int": NUM_INT, "num_dot": NUM_DOT, "num_frac": NUM_FRAC,
    "num_e": NUM_E, "num_esign": NUM_ESIGN, "num_exp": NUM_EXP, "done": DONE,
}
_LIT_MODE = {b"rue": LIT_RUE, b"ue": LIT_UE, b"e": LIT_E, b"alse": LIT_ALSE,
             b"lse": LIT_LSE, b"se": LIT_SE, b"ull": LIT_ULL, b"ll": LIT_LL,
             b"l": LIT_L}


def _build_base_masks() -> np.ndarray:
    """Static per-mode legal-byte masks [N_MODES, 256]. Dynamic bits (number
    terminators, the depth cap on open brackets, EOS) are OR'd/cleared at
    runtime in :func:`allowed_mask`."""
    m = np.zeros((N_MODES, 256), bool)

    def setb(mode, byts):
        for b in byts:
            m[mode, b] = True

    ws = bytes(host_json.WS)
    digits = bytes(host_json.DIGITS)
    value_start = bytes(host_json.VALUE_START)
    setb(FVALUE, ws + b"{")
    setb(VALUE, ws + value_start)
    setb(VALUE_OR_CLOSE, ws + value_start + b"]")
    setb(OBJ_FIRST, ws + b'"}')
    setb(OBJ_KEY, ws + b'"')
    setb(OBJ_COLON, ws + b":")
    setb(OBJ_AFTER, ws + b",}")
    setb(ARR_AFTER, ws + b",]")
    setb(STRING, bytes(host_json.STRING_BODY) + b'"\\')
    setb(STR_ESC, bytes(host_json.ESCAPABLE))
    for mode in (STR_U4, STR_U3, STR_U2, STR_U1):
        setb(mode, bytes(host_json.HEX))
    setb(NUM_SIGN, digits)
    setb(NUM_ZERO, ws + b".eE")
    setb(NUM_INT, ws + digits + b".eE")
    setb(NUM_DOT, digits)
    setb(NUM_FRAC, ws + digits + b"eE")
    setb(NUM_E, digits + b"+-")
    setb(NUM_ESIGN, digits)
    setb(NUM_EXP, ws + digits)
    for mode, ch in ((LIT_RUE, b"r"), (LIT_UE, b"u"), (LIT_E, b"e"),
                     (LIT_ALSE, b"a"), (LIT_LSE, b"l"), (LIT_SE, b"s"),
                     (LIT_ULL, b"u"), (LIT_LL, b"l"), (LIT_L, b"l")):
        setb(mode, ch)
    setb(DONE, ws)
    return m


_BASE_MASKS = _build_base_masks()
_WS_MASK = np.zeros((256,), bool)
for _b in host_json.WS:
    _WS_MASK[_b] = True


@struct.dataclass
class JsonDeviceState:
    mode: jax.Array      # i32 scalar
    depth: jax.Array     # i32 scalar
    stack: jax.Array     # [MAX_DEPTH] i32: 1 obj, 0 arr
    is_key: jax.Array    # bool scalar: the open string is an object key


def initial_state(force_object: bool = False) -> JsonDeviceState:
    return JsonDeviceState(
        mode=jnp.int32(FVALUE if force_object else VALUE),
        depth=jnp.int32(0),
        stack=jnp.zeros((MAX_DEPTH,), jnp.int32),
        is_key=jnp.bool_(False))


def encode_host_state(st: host_json.JsonState) -> JsonDeviceState:
    """Translate a host JsonState (e.g. after feeding a scaffold prefix)
    into the device encoding, so generation resumes mid-document."""
    if st.mode == "literal":
        mode = _LIT_MODE[bytes(st._literal_rest)]
    elif st.mode == "value" and st.force_object and not st.started:
        mode = FVALUE
    else:
        mode = _HOST_MODE[st.mode]
    if len(st.stack) > MAX_DEPTH:
        raise ValueError(f"scaffold nests deeper than MAX_DEPTH={MAX_DEPTH}")
    stack = np.zeros((MAX_DEPTH,), np.int32)
    for i, f in enumerate(st.stack):
        stack[i] = 1 if f == "obj" else 0
    return JsonDeviceState(
        mode=jnp.int32(mode), depth=jnp.int32(len(st.stack)),
        stack=jnp.asarray(stack), is_key=jnp.bool_(st._string_is_key))


def _is_done(st: JsonDeviceState) -> jax.Array:
    """Host ``JsonState.done``: DONE mode, or a top-level number terminal
    ("42" is a complete document)."""
    num_term = jnp.isin(st.mode, jnp.asarray(_NUM_TERMINAL))
    return (st.mode == DONE) | (num_term & (st.depth == 0))


def allowed_mask(st: JsonDeviceState, vocab_size: int,
                 eos_id: int) -> jax.Array:
    """[vocab_size] bool: legal next token ids (bytes 0..255 + EOS)."""
    base = jnp.asarray(_BASE_MASKS)[st.mode]                    # [256]
    top = jnp.where(st.depth > 0, st.stack[jnp.maximum(st.depth - 1, 0)], -1)
    num_term = jnp.isin(st.mode, jnp.asarray(_NUM_TERMINAL))
    # number terminators depend on the enclosing container
    base = base.at[ord(",")].set(base[ord(",")]
                                 | (num_term & (st.depth > 0)))
    base = base.at[ord("}")].set(base[ord("}")] | (num_term & (top == 1)))
    base = base.at[ord("]")].set(base[ord("]")] | (num_term & (top == 0)))
    # depth cap: no new containers at MAX_DEPTH (device-only restriction)
    at_cap = st.depth >= MAX_DEPTH
    base = base.at[ord("{")].set(base[ord("{")] & ~at_cap)
    base = base.at[ord("[")].set(base[ord("[")] & ~at_cap)
    mask = jnp.zeros((vocab_size,), bool).at[:256].set(base)
    return mask.at[eos_id].set(_is_done(st))


def feed(st: JsonDeviceState, b: jax.Array) -> JsonDeviceState:
    """Advance the automaton by one legal byte (jnp scalar ops only).
    Mirrors json_constrain.JsonState.feed byte-for-byte."""
    mode, depth, stack, is_key = st.mode, st.depth, st.stack, st.is_key
    top = jnp.where(depth > 0, stack[jnp.maximum(depth - 1, 0)], -1)
    is_ws = jnp.asarray(_WS_MASK)[b]
    num_term = jnp.isin(mode, jnp.asarray(_NUM_TERMINAL))

    def ctx_mode(d, t):
        # mode after completing a (non-key) value inside (d, top t)
        return jnp.where(d == 0, DONE, jnp.where(t == 1, OBJ_AFTER, ARR_AFTER))

    # ---- case A: a number terminates on ws / ',' / close -----------------
    a_close = num_term & ((b == ord("}")) | (b == ord("]")))
    a_comma = num_term & (b == ord(","))
    a_ws = num_term & is_ws
    a_any = a_close | a_comma | a_ws
    a_depth = jnp.where(a_close, depth - 1, depth)
    a_top = jnp.where(a_depth > 0, stack[jnp.maximum(a_depth - 1, 0)], -1)
    a_mode = jnp.where(
        a_comma, jnp.where(top == 1, OBJ_KEY, VALUE), ctx_mode(a_depth, a_top))

    # ---- case B: structural whitespace is a no-op ------------------------
    in_string = ((mode == STRING) | (mode == STR_ESC) | (mode == STR_U4)
                 | (mode == STR_U3) | (mode == STR_U2) | (mode == STR_U1))
    b_ws = is_ws & ~in_string & ~a_any

    # ---- case C: everything else, one branch per mode --------------------
    is_digit = (b >= ord("0")) & (b <= ord("9"))
    value_like = (mode == VALUE) | (mode == FVALUE) | (mode == VALUE_OR_CLOSE)

    # value starts
    push_obj = value_like & (b == ord("{"))
    push_arr = value_like & (b == ord("["))
    close_arr_now = (mode == VALUE_OR_CLOSE) & (b == ord("]"))
    c_mode = jnp.where(push_obj, OBJ_FIRST, mode)
    c_mode = jnp.where(push_arr, VALUE_OR_CLOSE, c_mode)
    c_mode = jnp.where(value_like & (b == ord('"')), STRING, c_mode)
    c_mode = jnp.where(value_like & (b == ord("-")), NUM_SIGN, c_mode)
    c_mode = jnp.where(value_like & (b == ord("0")), NUM_ZERO, c_mode)
    c_mode = jnp.where(value_like & is_digit & (b != ord("0")), NUM_INT, c_mode)
    c_mode = jnp.where(value_like & (b == ord("t")), LIT_RUE, c_mode)
    c_mode = jnp.where(value_like & (b == ord("f")), LIT_ALSE, c_mode)
    c_mode = jnp.where(value_like & (b == ord("n")), LIT_ULL, c_mode)

    # object / array punctuation
    key_start = (((mode == OBJ_FIRST) & (b == ord('"')))
                 | ((mode == OBJ_KEY) & (b == ord('"'))))
    c_mode = jnp.where(key_start, STRING, c_mode)
    c_mode = jnp.where((mode == OBJ_COLON) & (b == ord(":")), VALUE, c_mode)
    c_mode = jnp.where((mode == OBJ_AFTER) & (b == ord(",")), OBJ_KEY, c_mode)
    c_mode = jnp.where((mode == ARR_AFTER) & (b == ord(",")), VALUE, c_mode)

    # closers: pop, then complete into the surrounding context
    pop = (close_arr_now
           | ((mode == OBJ_FIRST) & (b == ord("}")))
           | ((mode == OBJ_AFTER) & (b == ord("}")))
           | ((mode == ARR_AFTER) & (b == ord("]"))))
    p_depth = depth - 1
    p_top = jnp.where(p_depth > 0, stack[jnp.maximum(p_depth - 1, 0)], -1)
    c_mode = jnp.where(pop, ctx_mode(p_depth, p_top), c_mode)

    # strings
    str_end = (mode == STRING) & (b == ord('"'))
    c_mode = jnp.where(str_end,
                       jnp.where(is_key, OBJ_COLON, ctx_mode(depth, top)),
                       c_mode)
    c_mode = jnp.where((mode == STRING) & (b == ord("\\")), STR_ESC, c_mode)
    c_mode = jnp.where((mode == STR_ESC),
                       jnp.where(b == ord("u"), STR_U4, STRING), c_mode)
    c_mode = jnp.where(mode == STR_U4, STR_U3, c_mode)
    c_mode = jnp.where(mode == STR_U3, STR_U2, c_mode)
    c_mode = jnp.where(mode == STR_U2, STR_U1, c_mode)
    c_mode = jnp.where(mode == STR_U1, STRING, c_mode)

    # numbers (non-terminating bytes)
    c_mode = jnp.where((mode == NUM_SIGN),
                       jnp.where(b == ord("0"), NUM_ZERO, NUM_INT), c_mode)
    in_int = (mode == NUM_ZERO) | (mode == NUM_INT)
    c_mode = jnp.where(in_int & (b == ord(".")), NUM_DOT, c_mode)
    is_e = (b == ord("e")) | (b == ord("E"))
    c_mode = jnp.where(in_int & is_e, NUM_E, c_mode)
    c_mode = jnp.where((mode == NUM_DOT), NUM_FRAC, c_mode)
    c_mode = jnp.where((mode == NUM_FRAC) & is_e, NUM_E, c_mode)
    c_mode = jnp.where((mode == NUM_E),
                       jnp.where((b == ord("+")) | (b == ord("-")),
                                 NUM_ESIGN, NUM_EXP), c_mode)
    c_mode = jnp.where((mode == NUM_ESIGN), NUM_EXP, c_mode)

    # literals: advance the chain; the last byte completes a value
    for frm, to in ((LIT_RUE, LIT_UE), (LIT_UE, LIT_E),
                    (LIT_ALSE, LIT_LSE), (LIT_LSE, LIT_SE), (LIT_SE, LIT_E),
                    (LIT_ULL, LIT_LL), (LIT_LL, LIT_L)):
        c_mode = jnp.where(mode == frm, to, c_mode)
    lit_done = (mode == LIT_E) | (mode == LIT_L)
    c_mode = jnp.where(lit_done, ctx_mode(depth, top), c_mode)

    # ---- merge the cases -------------------------------------------------
    new_mode = jnp.where(a_any, a_mode, jnp.where(b_ws, mode, c_mode))
    new_depth = jnp.where(a_any, a_depth,
                          jnp.where(b_ws, depth,
                                    jnp.where(pop, p_depth,
                                              jnp.where(push_obj | push_arr,
                                                        depth + 1, depth))))
    write_slot = jnp.minimum(depth, MAX_DEPTH - 1)
    new_stack = jnp.where(
        ~a_any & ~b_ws & (push_obj | push_arr),
        stack.at[write_slot].set(jnp.where(push_obj, 1, 0)), stack)
    new_is_key = jnp.where(~a_any & ~b_ws,
                           jnp.where(key_start, True,
                                     jnp.where(str_end, False, is_key)),
                           is_key)
    return JsonDeviceState(mode=jnp.int32(new_mode),
                           depth=jnp.int32(new_depth),
                           stack=new_stack, is_key=new_is_key)
