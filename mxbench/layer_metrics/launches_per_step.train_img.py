"""Host loop: compiled programs the training path handed to the runtime
a step (``mx_program_launches_total``, all paths, as ``mark_step``
closes the count into the step log), over the untraced window that a
traced run makes first. The fused Gluon step is one. None where the
program keeps no step log."""
UNIT = "count/step"


def read(run):
    per = run.untraced_s_per_step
    if not per or not per.get("step_log_steps"):
        return None
    return per["launches"]
