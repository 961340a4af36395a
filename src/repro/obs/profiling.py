"""Optional ``jax.profiler`` capture window for the serve loop
(DESIGN.md §13).

``--profile-dir PATH`` on the launcher arms a ``ProfileWindow``: the
first decode step after arming starts a ``jax.profiler`` trace, and the
window stops it after N steps (or at serve teardown, whichever comes
first).  The resulting TensorBoard-loadable trace shows device-side
kernel timing that the host-side ``StepTracer`` cannot see — the two
line up via step numbers.

Stop is idempotent: the scheduler calls ``stop()`` both when the window
elapses and unconditionally in its ``finally`` teardown.  A requested
profile that cannot start or stop raises — the window disarms first, so
the teardown ``stop()`` is a no-op — rather than letting the trace vanish
from a run that otherwise succeeds.
"""
from __future__ import annotations

from typing import Optional


class ProfileWindow:
    """Capture ``n_steps`` serve steps into a jax.profiler trace under
    ``log_dir``.  Inert when ``log_dir`` is empty."""

    def __init__(self, log_dir: str = "", n_steps: int = 8):
        if n_steps < 1:
            raise ValueError(f"profile window needs n_steps >= 1, got {n_steps}")
        self.log_dir = log_dir
        self.n_steps = n_steps
        self.steps_seen = 0
        self.active = False
        self.done = not log_dir

    def on_step(self) -> None:
        """Called once per serve step; drives the start->capture->stop arc."""
        if self.done:
            return
        if not self.active:
            import jax

            try:
                jax.profiler.start_trace(self.log_dir)
            except Exception as e:
                self.done = True
                raise RuntimeError(f"profile into {self.log_dir!r} could not start: {e}") from e
            self.active = True
        self.steps_seen += 1
        if self.steps_seen >= self.n_steps:
            self.stop()

    def stop(self) -> None:
        if not self.active:
            self.done = True
            return
        self.active = False
        self.done = True
        import jax

        jax.profiler.stop_trace()


def make_profile_window(log_dir: str = "", n_steps: int = 8) -> Optional[ProfileWindow]:
    """A window when ``log_dir`` is set, else None (scheduler skips the hook)."""
    return ProfileWindow(log_dir, n_steps) if log_dir else None
