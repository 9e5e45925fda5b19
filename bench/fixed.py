"""Settings a configuration states that the program fixes in code.

The reference reads every setting from the configuration; the program
takes some of them as constants and no setting. A layer refuses a
configuration whose stated value differs from the program's constant, so
that such an edit stops the run at set-up instead of moving the reference
alone and reading as a wrong program."""


def refuse_unless(key: str, stated, fixed) -> None:
    """Raise unless the configuration's `key` states the program's value."""
    if stated != fixed:
        raise ValueError(f"{key} is {stated!r} in the configuration, but the "
                         f"program fixes it at {fixed!r} and takes no setting "
                         f"for it")
