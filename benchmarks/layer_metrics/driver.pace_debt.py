"""How far the paced learner fell behind the preset's replay ratio in
the window: 1 - grad steps / (steps_per_frame_cap * frames ingested),
in %. About 0 while the learner keeps up with its pacing; positive
when something (the state lock, the chip) holds it back."""


def read(facts: dict) -> float | None:
    w = facts.get("window_counters")
    if not w or w["frames"] <= 0:
        return None
    owed = facts["steps_per_frame_cap"] * w["frames"]
    return 100.0 * (1.0 - w["grad_steps"] / owed)
