"""The one recorder of which form each part of the step took (obs/forms.py):
what a `recording()` hears, and what it does not."""

import threading

import jax
import jax.numpy as jnp

from galvatron_tpu.obs import forms


def test_a_recording_holds_only_what_was_traced_inside_it():
    forms.took(forms.DELTA_RULE, "xla")  # before: nobody records, nothing is kept
    with forms.recording() as took:
        assert took == {}
        forms.took(forms.DELTA_RULE, "pallas")
        forms.took(forms.DELTA_RULE, "pallas")
        forms.took(forms.EXPERT_WINDOW, 1536)  # a form is said as a string, as the event's JSON has it
    forms.took(forms.DELTA_RULE, "xla")  # after
    assert took == {forms.DELTA_RULE: {"pallas": 2}, forms.EXPERT_WINDOW: {"1536": 1}}
    # a part that was not heard reads as no forms, a form not taken as 0, and neither is stored by the reading
    assert took[forms.DELTA_RULE]["xla"] == 0 and not took[forms.MOE_ROWS]
    assert set(took) == {forms.DELTA_RULE, forms.EXPERT_WINDOW} and set(took[forms.DELTA_RULE]) == {"pallas"}


def test_two_recordings_one_after_the_other_each_read_their_own():
    with forms.recording() as first:
        forms.took(forms.MOE_ROWS, "kernel")
    with forms.recording() as second:
        forms.took(forms.MOE_ROWS, "xla")
        forms.took(forms.GATED_KERNEL_GRADS, "as_stored", key=(0, "wi"))
    assert first == {forms.MOE_ROWS: {"kernel": 1}}
    assert second == {forms.MOE_ROWS: {"xla": 1}, forms.GATED_KERNEL_GRADS: {"as_stored": 1}}


def test_a_nested_recording_reads_its_own_and_the_outer_one_hears_it_too():
    with forms.recording() as outer:
        forms.took(forms.WINDOW_ATTENTION, "xla")
        with forms.recording() as inner:
            forms.took(forms.WINDOW_ATTENTION, "pallas")
            forms.took(forms.SCAN_GRADS, "zero_layout", key="a")
        forms.took(forms.SCAN_GRADS, "zero_layout", key="a")  # the outer one met this leaf inside already
        forms.took(forms.WINDOW_ATTENTION, "xla")
    assert inner == {forms.WINDOW_ATTENTION: {"pallas": 1}, forms.SCAN_GRADS: {"zero_layout": 1}}
    assert outer == {forms.WINDOW_ATTENTION: {"xla": 2, "pallas": 1}, forms.SCAN_GRADS: {"zero_layout": 1}}


def test_a_key_met_twice_counts_once_and_a_call_without_one_every_time():
    with forms.recording() as took:
        for _ in range(3):  # the first forward, the recomputation, a second microbatch: the same leaf
            forms.took(forms.SCAN_GRADS, "zero_layout", key=(0, "['wi']['kernel']"))
            forms.took(forms.SELECTIVE_SCAN, "xla")
        forms.took(forms.SCAN_GRADS, "zero_layout", key=(1, "['wi']['kernel']"))  # another run's
    assert took == {forms.SCAN_GRADS: {"zero_layout": 2}, forms.SELECTIVE_SCAN: {"xla": 3}}
    with forms.recording() as again:  # the keys a recording met are its own
        forms.took(forms.SCAN_GRADS, "zero_layout", key=(0, "['wi']['kernel']"))
    assert again == {forms.SCAN_GRADS: {"zero_layout": 1}}


def test_a_trace_on_another_thread_is_not_heard():
    def beside():
        forms.took(forms.TABLE_LOOKUP, "rows_over_dp")
        with forms.recording() as own:
            forms.took(forms.TABLE_LOOKUP, "table_whole")
        heard.append(own)

    heard = []
    with forms.recording() as took:
        thread = threading.Thread(target=beside)
        thread.start()
        thread.join()
        forms.took(forms.VOCAB_SPLIT, "pp,m0")
    assert took == {forms.VOCAB_SPLIT: {"pp,m0": 1}}
    assert heard == [{forms.TABLE_LOOKUP: {"table_whole": 1}}]


def test_what_a_cached_trace_does_not_run_again_is_not_said_again():
    @jax.jit
    def rule(x):
        forms.took(forms.KDA_RULE, "xla")
        return x + 1

    with forms.recording() as first:
        rule(jnp.zeros(3))
    with forms.recording() as second:
        rule(jnp.zeros(3))  # jax holds the trace: the body does not run
        rule(jnp.zeros(4))  # another shape: it does
    assert first == second == {forms.KDA_RULE: {"xla": 1}}


def test_the_part_names_are_said_once():
    parts = [value for name, value in vars(forms).items() if name.isupper()]
    assert parts and len(parts) == len(set(parts)) and all(isinstance(part, str) for part in parts)
